"""The ladder of step sizes under two kinds of page (Mellum2), under a
recurrent state beside the pages (Nemotron-H) and under both a third
pool a page and a recurrent state (MiniCPM-SALA): all three layouts
state a tile, so their ladders have rungs.  The same check as
``test_serving_ladder.py``'s, in a file of its own so that another
worker takes these engines' compiles."""

import numpy as np
import pytest

from serving_ladder_toys import (  # noqa: F401 - compiles is a fixture
    LAYOUTS, SALA_SCAN_TILE, check_a_ladder_serves_what_the_top_rung_serves,
    check_a_re_ask_late_hits_what_its_layout_can_restore, compiles, one_rung)


@pytest.mark.parametrize("name", ["mellum2", "nemotron", "minicpm_sala"])
def test_a_ladder_serves_what_the_top_rung_serves(name, compiles,
                                                  monkeypatch):
    if name == "mellum2":
        check_a_ladder_serves_what_the_top_rung_serves(name, compiles)
        return
    if name == "nemotron":
        steps, _ = check_a_ladder_serves_what_the_top_rung_serves(
            name, compiles)
        # the trace had what a state can get wrong between rungs:
        # snapshots taken, one restored, every row through the scan
        assert steps["state_restored_tokens"]
        assert steps["state_snapshots_taken"]
        assert steps["ssm_rows"] == steps["rows"]
        return
    from paddle_tpu.models import minicpm_sala

    monkeypatch.setattr(minicpm_sala, "SCAN_TILE_ROWS", SALA_SCAN_TILE)
    steps, extras = check_a_ladder_serves_what_the_top_rung_serves(
        name, compiles)
    # the trace had what this step can get wrong at a smaller rung: rows
    # on both sides of ``dense_len``, selections to compare, compressed
    # keys scored, a state restored from a snapshot, snapshots taken
    assert steps["sparse_rows"] and steps["dense_rows"]
    assert steps["sel_blocks"] and steps["ckey_ctx"]
    assert steps["state_restored_tokens"] and steps["state_snapshots_taken"]
    assert any(len(e) == 1 and e[0].size for e in extras)


@pytest.mark.parametrize("name", ["mellum2", "nemotron", "minicpm_sala"])
def test_a_re_ask_late_hits_what_its_layout_can_restore(name, monkeypatch):
    """A window kind of page (Mellum2's toy) and a recurrent state
    (Nemotron-H's, MiniCPM-SALA's): a block the cache took before its
    prompt was done is not restorable, so a re-ask finds nothing while
    its document prefills, as before, and late-hits once it is done."""
    if name == "minicpm_sala":
        from paddle_tpu.models import minicpm_sala

        monkeypatch.setattr(minicpm_sala, "SCAN_TILE_ROWS", SALA_SCAN_TILE)
    check_a_re_ask_late_hits_what_its_layout_can_restore(name)


def _serve_across_rungs(eng, compiles=None):
    """A trace that carries a state from the top rung to the lowest:
    while ``b`` decodes, ``a``'s prompt of two whole chunks is prefilled
    beside it (the top rung; a snapshot at each chunk's end) and ends
    with its first token; ``c`` continues ``a``'s prompt by three
    tokens and ``d`` is new.  Returns the tokens by request's name, a
    launch its packed rows and counts, and each request's
    ``prefill_stats``."""
    rng = np.random.default_rng(43)
    vocab = eng.cfg.vocab_size
    shared = rng.integers(1, vocab, 2 * eng.prefill_budget)
    packed = []
    pack = eng._pack_unified

    def spy(*a, **k):
        rows, gather, launch = pack(*a, **k)
        if launch.counts["rows"]:
            packed.append((rows[:launch.counts["rows"]].copy(),
                           launch.counts))
        return rows, gather, launch

    eng._pack_unified = spy
    rids = {}

    def add(name, prompt, new):
        rids[name] = eng.add_request(np.asarray(prompt, np.int32),
                                     max_new_tokens=new)

    def drain(until):
        while until():
            eng.step()

    add("b", rng.integers(1, vocab, 6), 40)
    eng.step()                          # the engine's first launch
    after_first = None if compiles is None else compiles[0]
    eng.step()
    add("a", shared, 1)
    drain(lambda: len(eng.finished) < 1)
    add("c", np.concatenate([shared, rng.integers(1, vocab, 3)]), 3)
    drain(lambda: len(eng.finished) < 2)
    add("d", rng.integers(1, vocab, 5), 3)
    drain(lambda: eng.queue or eng.active.any())
    if compiles is not None:
        # no program is compiled after the padding launches, which the
        # engine's first launch follows
        assert compiles[0] == after_first
    tokens = {f.rid: f.tokens.tolist() for f in eng.run()}
    return ({n: tokens[rid] for n, rid in rids.items()}, packed,
            {n: eng.prefill_stats[rid] for n, rid in rids.items()})


def test_a_state_written_at_the_top_rung_is_read_at_the_lowest(compiles):
    """Nemotron-H's toy over its ladder 16 / 32 / 35: a launch of decode
    rows alone takes ``ladder[0]``, nothing is compiled after the
    padding launches, and the rungs' programs share the state pools: a
    snapshot the top rung's program wrote is restored by the lowest
    rung's, into the slot whose last tenant's state the top rung wrote,
    and a request that starts from zeros takes a slot a tenant has left;
    tokens and states are those of the top rung alone."""
    build, ladder = LAYOUTS["nemotron"]
    make = build()
    eng = make()
    assert eng.ladder == ladder
    tokens, packed, stats = _serve_across_rungs(eng, compiles)
    steps = eng.serving_stats()["steps"]
    state = [np.asarray(p) for kind in eng.state for p in kind]
    eng.shutdown()

    lowest, top = ladder[0], ladder[-1]
    for rows, counts in packed:
        assert counts["rows_cap"] == min(n for n in ladder if n >= len(rows))
        if not counts["prefill_rows"]:
            assert counts["rows_cap"] == lowest
    assert sum(1 for _, c in packed if not c["prefill_rows"]) >= 30
    assert steps["launches_by_rows"][lowest] >= 30
    assert steps["launches_by_rows"][top] >= 2
    # the rung that wrote each snapshot entry (a slot's last row names
    # it in the last column), and the rung of each launch that restores
    # from one (a row's sixth column: an entry past the slots' own)
    slots = eng.max_slots
    launches = [(rows, c["rows_cap"]) for rows, c in packed]
    wrote = {int(r[7]): rung for rows, rung in launches
             for r in rows if r[7] >= 0}
    restores = [(i, int(r[5]), int(r[4]))
                for i, (rows, _) in enumerate(launches)
                for r in rows if r[5] >= slots]
    assert restores and stats["c"]["state_restored_tokens"] \
        == 2 * eng.prefill_budget
    for i, entry, slot in restores:
        assert wrote[entry] == top and launches[i][1] == lowest
        # the slot's tenant before wrote its state at the top rung last
        before = [rung for rows, rung in launches[:i]
                  if (rows[:, 4] == slot).any()]
        assert before and before[-1] == top
    # ``d`` starts from zeros, at the lowest rung, in a slot that ``a``
    # and ``c`` have left
    fresh = [(int(r[4]), rung) for rows, rung in launches[restores[-1][0]:]
             for r in rows if r[5] < 0]
    assert fresh and all(rung == lowest for _, rung in fresh)
    assert {s for s, _ in fresh} <= {s for _, _, s in restores}

    alone = one_rung(make())            # the same engine, one rung
    want, want_packed, _ = _serve_across_rungs(alone)
    want_state = [np.asarray(p) for kind in alone.state for p in kind]
    alone.shutdown()
    assert tokens == want and len(packed) == len(want_packed)
    assert [len(t) for t in tokens.values()] == [40, 1, 3, 3]
    # every entry but the trash entry, which padding rows write
    for got, exp in zip(state, want_state, strict=True):
        np.testing.assert_allclose(got[:-1], exp[:-1], rtol=1e-5, atol=1e-6)
