"""Distributed core: ProcessMesh, placements, shard_tensor/reshard,
topology, functional collectives (8 virtual CPU devices; SURVEY.md §4
takeaway — host-platform fake devices replace subprocess-per-GPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import Partial, Replicate, Shard
from jax import shard_map


def test_process_mesh_basics():
    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "mp"])
    assert mesh.shape == [2, 4]
    assert mesh.dim_names == ["dp", "mp"]
    assert mesh.process_ids == list(range(8))
    assert mesh.get_dim_size("mp") == 4
    sub = mesh.get_mesh_with_dim("mp")
    assert sub.dim_names == ["mp", "dp"]
    jm = mesh.get_jax_mesh()
    assert jm.shape == {"dp": 2, "mp": 4}


def test_shard_tensor_and_placements():
    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "mp"])
    x = paddle.ones([8, 16], dtype="float32")
    d = dist.shard_tensor(x, mesh, [Shard(0), Shard(1)])
    assert dist.is_dist(d)
    assert d.shape == [8, 16]  # global logical shape
    pl = dist.get_placements(d)
    assert pl[0] == Shard(0) and pl[1] == Shard(1)
    # each device holds an 4x4 shard
    shard = d._value.addressable_shards[0]
    assert shard.data.shape == (4, 4)


def test_reshard_s_to_r_and_r_to_s():
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    data = np.random.rand(8, 8).astype(np.float32)
    d = dist.shard_tensor(paddle.to_tensor(data), mesh, [Shard(0)])
    r = dist.reshard(d, mesh, [Replicate()])
    np.testing.assert_allclose(np.asarray(r._value), data, rtol=1e-6)
    s = dist.reshard(r, mesh, [Shard(1)])
    assert dist.get_placements(s)[0] == Shard(1)
    np.testing.assert_allclose(np.asarray(s._value), data, rtol=1e-6)


def test_partial_resolution():
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    from paddle_tpu.distributed.auto_parallel.api import mark_partial
    # per-device partials: replicated array of ones, tagged partial → psum = 8
    x = dist.shard_tensor(paddle.ones([4]), mesh, [Replicate()])
    mark_partial(x, ["x"])
    r = dist.reshard(x, mesh, [Replicate()])
    np.testing.assert_allclose(np.asarray(r._value), np.full((4,), 8.0))


def test_unshard_and_local():
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    data = np.arange(16, dtype=np.float32).reshape(8, 2)
    d = dist.shard_tensor(paddle.to_tensor(data), mesh, [Shard(0)])
    local = dist.dtensor_to_local(d)
    assert local.shape == [1, 2]
    full = dist.unshard_dtensor(d)
    np.testing.assert_allclose(np.asarray(full._value), data)


def test_topology_hcg():
    hcg = dist.HybridCommunicateGroup(dp_degree=2, mp_degree=2, pp_degree=2,
                                      sharding_degree=1, sep_degree=1)
    assert hcg.get_data_parallel_world_size() == 2
    assert hcg.get_model_parallel_world_size() == 2
    assert hcg.get_pipe_parallel_world_size() == 2
    assert hcg.mesh.shape["mp"] == 2
    topo = hcg.topology
    assert topo.world_size() == 8
    # mp is the innermost axis → mp groups are contiguous ranks
    mp_groups = topo.get_comm_list("mp")
    assert mp_groups[0] == [0, 1]
    assert len(mp_groups) == 4
    g = hcg.get_model_parallel_group()
    assert g.nranks == 2


def test_functional_collectives_shard_map():
    import paddle_tpu.distributed.functional as F
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.asarray(jax.devices()[:8], dtype=object)
    mesh = Mesh(devs, axis_names=("g",))
    x = jnp.arange(8.0)

    def ar(v):
        return F.all_reduce(v, axis="g")

    out = jax.jit(shard_map(ar, mesh=mesh, in_specs=(P("g"),),
                                out_specs=P("g")))(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8,), 28.0))

    def ag(v):
        return F.all_gather(v, axis="g", concat_dim=0)

    # all_gather output is typed axis-varying in jax's vma system even
    # though its value is replicated — check_vma=False asserts our intent
    out = jax.jit(shard_map(ag, mesh=mesh, in_specs=(P("g"),),
                                out_specs=P(None), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0))

    def rs(v):
        return F.reduce_scatter(v, axis="g", scatter_dim=0)

    y = jnp.ones((8, 8))
    out = jax.jit(shard_map(rs, mesh=mesh, in_specs=(P(None, None),),
                                out_specs=P("g", None)))(y)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 8), 8.0))

    def a2a(v):
        return F.all_to_all(v, axis="g", split_dim=0, concat_dim=1)

    # each rank holds (8, 1); after a2a over split_dim=0/concat_dim=1 each
    # rank holds (1, 8) = its row of the global matrix transpose-of-chunks
    z = jnp.arange(64.0).reshape(8, 8)
    out = jax.jit(shard_map(a2a, mesh=mesh, in_specs=(P(None, "g"),),
                                out_specs=P("g", None)))(z)
    np.testing.assert_allclose(np.asarray(out), np.asarray(z))

    def bc(v):
        return F.broadcast(v, src=3, axis="g")

    out = jax.jit(shard_map(bc, mesh=mesh, in_specs=(P("g"),),
                                out_specs=P("g")))(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8,), 3.0))

    def sh(v):
        return F.shift(v, offset=1, axis="g")

    out = jax.jit(shard_map(sh, mesh=mesh, in_specs=(P("g"),),
                                out_specs=P("g")))(x)
    # rank i sends to i+1 → output[i] = x[i-1]
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))


def test_eager_collectives():
    mesh = dist.ProcessMesh(np.arange(8), ["x"])

    # all_gather on a sharded tensor
    data = np.random.rand(8, 3).astype(np.float32)
    d = dist.shard_tensor(paddle.to_tensor(data), mesh, [Shard(0)])
    gathered = []
    from paddle_tpu.distributed.collective import Group
    gx = Group(mesh.get_jax_mesh(), "x", 99, list(range(8)))
    full = dist.all_gather(gathered, d, group=gx)
    assert len(gathered) == 8
    np.testing.assert_allclose(np.asarray(full._value), data, rtol=1e-6)

    # all_reduce on a partial tensor
    from paddle_tpu.distributed.auto_parallel.api import mark_partial
    x = dist.shard_tensor(paddle.ones([4]), mesh, [Replicate()])
    mark_partial(x, ["x"])
    dist.all_reduce(x, group=gx)
    np.testing.assert_allclose(np.asarray(x._value), np.full((4,), 8.0))
    assert not x._partial_axes


def test_reduce_scatter_partial_and_prod():
    from paddle_tpu.distributed.collective import Group
    from paddle_tpu.distributed.auto_parallel.api import mark_partial
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    gx = Group(mesh.get_jax_mesh(), "x", 98, list(range(8)))

    # reduce_scatter must resolve pending-Partial inputs
    x = dist.shard_tensor(paddle.ones([8]), mesh, [Replicate()])
    mark_partial(x, ["x"])
    out = paddle.zeros([8])
    dist.reduce_scatter(out, x, group=gx)
    np.testing.assert_allclose(np.asarray(out._value), np.full((8,), 8.0))

    # PROD on a sharded tensor (incl. negatives) must be exact
    vals = np.array([1., -2., 3., 1., 1., 2., 1., 2.], dtype=np.float32)
    d = dist.shard_tensor(paddle.to_tensor(vals), mesh, [Shard(0)])
    dist.all_reduce(d, op=dist.ReduceOp.PROD, group=gx)
    np.testing.assert_allclose(np.asarray(d._value), np.full((8,), vals.prod()))

    # raw jax array input: returns value, no mutation attempt
    raw = dist.shard_tensor(paddle.to_tensor(vals), mesh, [Shard(0)])._value
    res = dist.all_reduce(raw, op=dist.ReduceOp.SUM, group=gx)
    np.testing.assert_allclose(np.asarray(res), np.full((8,), vals.sum()))


def test_process_mesh_getitem_names():
    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "mp"])
    sub = mesh[:, 0]
    assert sub.dim_names == ["dp"]
    assert sub.process_ids == [0, 4]
    sub2 = mesh[1]
    assert sub2.dim_names == ["mp"]
    assert sub2.process_ids == [4, 5, 6, 7]


def test_shard_layer_keeps_param_identity():
    mesh = dist.ProcessMesh(np.arange(8), ["x"])
    layer = paddle.nn.Linear(8, 8)
    before = layer.parameters()
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=before)
    dist.shard_layer(layer, mesh)
    after = layer.parameters()
    assert all(a is b for a, b in zip(before, after))
    x = paddle.rand([4, 8])
    loss = (layer(x) ** 2).mean()
    loss.backward()
    w_before = np.asarray(before[0]._value).copy()
    opt.step()
    assert not np.allclose(np.asarray(before[0]._value), w_before)


def test_sharded_eager_ops_propagate():
    """Eager ops on DTensors propagate shardings via GSPMD — the analog of
    the reference's generated dist branch (dist_api_gen.py:46) without
    codegen."""
    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "mp"])
    a = dist.shard_tensor(paddle.rand([8, 16]), mesh, [Shard(0), Replicate()])
    w = dist.shard_tensor(paddle.rand([16, 32]), mesh, [Replicate(), Shard(1)])
    out = paddle.matmul(a, w)
    ref = np.asarray(a._value) @ np.asarray(w._value)
    np.testing.assert_allclose(np.asarray(out._value), ref, rtol=1e-4)


def test_shard_optimizer_stage3():
    mesh = dist.ProcessMesh(np.arange(8), ["dp"])
    dist.set_mesh(mesh)
    layer = paddle.nn.Linear(16, 16)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=layer.parameters())
    opt = dist.shard_optimizer(opt, dist.ShardingStage3(mesh, axis="dp"))
    # params now sharded on dim 0
    w = layer.parameters()[0]
    assert dist.is_dist(w)
    assert dist.get_placements(w)[0] == Shard(0)
    x = paddle.rand([4, 16])
    loss = (layer(x) ** 2).mean()
    loss.backward()
    opt.step()
    # optimizer state (moment1) is sharded too
    st = opt._state[id(w)]
    s = st["moment1"].sharding
    from jax.sharding import NamedSharding
    assert isinstance(s, NamedSharding)
    assert tuple(s.spec) and s.spec[0] == "dp"


def test_shard_optimizer_stage2_grad_reshard():
    """Stage 2's distinction from stage 1: an eager grad re-placement hook
    puts gradients in the Shard(0) (reduce-scatter) layout pre-update,
    without changing the update's numbers."""
    from jax.sharding import NamedSharding

    mesh = dist.ProcessMesh(np.arange(8), ["dp"])
    dist.set_mesh(mesh)

    def run(stage_cls):
        paddle.seed(0)
        layer = paddle.nn.Linear(16, 16)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=layer.parameters())
        opt = dist.shard_optimizer(opt, stage_cls(mesh, axis="dp"))
        x = paddle.to_tensor(np.random.RandomState(0).rand(4, 16)
                             .astype(np.float32))
        loss = (layer(x) ** 2).mean()
        loss.backward()
        opt.step()
        return layer, opt

    l1, o1 = run(dist.ShardingStage1)
    l2, o2 = run(dist.ShardingStage2)
    assert o1._grad_transform is None
    assert o2._grad_transform is not None
    # identical update results (one step each)
    for p1, p2 in zip(l1.parameters(), l2.parameters()):
        np.testing.assert_allclose(np.asarray(p1._value),
                                   np.asarray(p2._value), rtol=1e-6)
    # the hook re-places a replicated grad into Shard(0)
    w = l2.parameters()[0]
    g = paddle.to_tensor(np.ones(tuple(w.shape), np.float32))
    rg = o2._grad_transform(w, g)
    s = rg._value.sharding
    assert isinstance(s, NamedSharding) and s.spec[0] == "dp"
    # write-back realized the memory effect: the surviving p._grad after a
    # step is in the sharded layout, not the replicated one
    loss2 = (l2(paddle.to_tensor(np.ones((4, 16), np.float32))) ** 2).mean()
    loss2.backward()
    o2.step()
    gs = w._grad._value.sharding
    assert isinstance(gs, NamedSharding) and gs.spec[0] == "dp"
    # a bad axis fails at install time, not silently per-grad
    import pytest as _pytest
    l3 = paddle.nn.Linear(8, 8)
    o3 = paddle.optimizer.SGD(learning_rate=0.1, parameters=l3.parameters())
    with _pytest.raises(ValueError):
        dist.shard_optimizer(o3, dist.ShardingStage2(mesh, axis="data"))


# ------------------------------------------------------------- SPMD rules


def test_spmd_rule_matmul_propagation():
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.auto_parallel import infer_forward

    # row-sharded x, column-sharded y: no conflict
    (ix, iy), (out,), meta = infer_forward("matmul", P("dp", None),
                                           P(None, "mp"))
    assert tuple(out) == ("dp", "mp")
    assert meta["partial_axes"] == ()
    # agreeing contraction shard -> pending partial over mp
    (ix, iy), (out,), meta = infer_forward("matmul", P(None, "mp"),
                                           P("mp", None))
    assert meta["partial_axes"] == ("mp",)
    # disagreeing contraction shard -> k replicated on both sides
    (ix, iy), (out,), meta = infer_forward("matmul", P(None, "mp"),
                                           P("dp", None))
    assert tuple(ix)[-1] is None and tuple(iy)[0] is None
    assert meta["partial_axes"] == ()


def test_spmd_rule_registered_on_opdef():
    from paddle_tpu.ops.registry import get_op

    assert get_op("matmul").spmd_rule is not None
    assert get_op("add").spmd_rule is not None


def test_shard_op_applies_constraints():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed.auto_parallel import shard_op

    devs = np.asarray(jax.devices()[:8], dtype=object).reshape(2, 4)
    mesh = jax.sharding.Mesh(devs, ("dp", "mp"))
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(8, 16).astype("float32"))
    y = paddle.to_tensor(np.random.RandomState(1)
                         .randn(16, 12).astype("float32"))
    out = shard_op("matmul", mesh, x, y,
                   rule_kwargs=None)
    np.testing.assert_allclose(np.asarray(out._value),
                               np.asarray(x._value) @ np.asarray(y._value),
                               rtol=1e-3, atol=1e-5)

    # with sharded inputs the output carries the propagated spec
    xs = paddle.to_tensor(jax.device_put(x._value,
                                         NamedSharding(mesh, P("dp", None))))
    ys = paddle.to_tensor(jax.device_put(y._value,
                                         NamedSharding(mesh, P(None, "mp"))))
    out2 = shard_op("matmul", mesh, xs, ys)
    spec = out2._value.sharding.spec
    assert tuple(spec) == ("dp", "mp")
    np.testing.assert_allclose(np.asarray(out2._value),
                               np.asarray(x._value) @ np.asarray(y._value),
                               rtol=1e-3, atol=1e-5)


def test_c_collective_ops_with_group():
    """The c_* static-graph op family (ops/yaml/_impl.py) routes through
    the eager collective layer when a group exists: c_concat gathers along
    the LAST axis (column-parallel inverse of c_split), c_scatter's
    per-rank result rides Shard(0)."""
    from paddle_tpu.ops import generated as G

    dist.init_parallel_env()
    n = dist.get_world_size()
    x = paddle.to_tensor(np.arange(8, dtype=np.float32).reshape(2, 4))

    r = G.c_allreduce_sum(x)  # replicated: identity
    np.testing.assert_allclose(np.asarray(r._value), np.asarray(x._value))

    cat = G.c_concat(x, nranks=n)
    assert tuple(cat.shape) == (2, 4 * n)  # last-axis gather

    big = paddle.to_tensor(np.arange(n * 3, dtype=np.float32).reshape(n, 3))
    sc = G.c_scatter(big, nranks=n)
    assert tuple(sc.shape) == (1, 3)
