"""Where a model meets the serving engine: every arrow points one way.

    models/llama_paged.py  deepseek_v32.py  nemotron_h.py  minicpm_sala.py
                \\              |               |              /   kimi_linear.py
                 v             v               v             v       v
                    inference/paged_layout.py   (the seam)
                                  ^
                                  |
      inference/serving.py (the scheduler) ---> inference/page_cache.py

The sources are read with ``ast``; no engine is built.
"""
import ast
import inspect
import pathlib

import pytest

PKG = pathlib.Path(__file__).resolve().parents[1] / "paddle_tpu"


def _imports(rel):
    """Every module a file imports, at any depth of nesting, as the
    dotted path from the package's root (``inference.serving``) or the
    outside module's name; and the names taken from each."""
    path = PKG / rel
    here = ("paddle_tpu", *path.relative_to(PKG).with_suffix("").parts)
    found = {}
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for a in node.names:
                found.setdefault(a.name, set())
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - node.level] if node.level else ()
            mod = ".".join((*base, *(node.module or "").split("."))).strip(".")
            names = {a.name for a in node.names}
            if mod.startswith("paddle_tpu"):
                mod = mod[len("paddle_tpu"):].lstrip(".")
                # ``from . import x`` names modules, not attributes
                if not node.module:
                    for n in names:
                        found.setdefault(f"{mod}.{n}".strip("."), set())
                    continue
            found.setdefault(mod, set()).update(names)
    return found


def test_the_seam_imports_no_model_and_no_engine():
    mods = _imports("inference/paged_layout.py")
    assert not [m for m in mods if m.startswith("models")
                or m == "inference.serving"], sorted(mods)


def test_the_page_cache_is_host_code():
    mods = _imports("inference/page_cache.py")
    assert not [m for m in mods if m.split(".")[0] in ("jax", "jaxlib")]
    # (the one name it takes from the seam is for a type checker alone)
    assert not [m for m in mods if m.startswith("models")
                or m == "inference.serving"], sorted(mods)


@pytest.mark.parametrize("rel", sorted(
    p.relative_to(PKG).as_posix() for p in (PKG / "models").glob("*.py")))
def test_no_model_imports_the_engine(rel):
    assert "inference.serving" not in _imports(rel)


def test_the_engine_holds_no_model():
    text = (PKG / "inference/serving.py").read_text()
    assert "named_scope" not in text
    mods = _imports("inference/serving.py")
    models = {m: names for m, names in mods.items()
              if m.startswith("models")}
    # the config registry (a config's id and its rope tables) and nothing
    # else of any model: the rest comes through ``cfg.paged_layout()``
    assert models == {"models.generation": {"_CFGS", "register_config"}}
    assert text.count("\n") < 2000


def _configs():
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.models.deepseek_v32 import DeepseekV32Config
    from paddle_tpu.models.kimi_linear import KimiLinearConfig
    from paddle_tpu.models.mellum2 import Mellum2Config
    from paddle_tpu.models.minicpm_sala import MiniCPMSALAConfig
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    return {"llama": LlamaConfig.debug, "mellum2": Mellum2Config.debug,
            "deepseek_v32": DeepseekV32Config.debug,
            "nemotron_h": NemotronHConfig.debug,
            "minicpm_sala": MiniCPMSALAConfig.debug,
            "kimi_linear": KimiLinearConfig.debug}


#: ``PagedLayout.step``'s signature (``inference/paged_layout.py``)
STEP = ("params", "k_pages", "v_pages", "rows", "tables", "cos_tab",
        "sin_tab", "self_cfg_id", "pages_per_step", "kv_scales", "with_head",
        "gather", "prev_tokens")


@pytest.mark.parametrize("name", ["llama", "mellum2", "deepseek_v32",
                                  "nemotron_h", "minicpm_sala",
                                  "kimi_linear"])
def test_every_layouts_step_takes_the_seams_signature(name):
    from paddle_tpu.inference.paged_layout import PagedLayout

    layout = _configs()[name]().paged_layout()
    assert isinstance(layout, PagedLayout)
    sig = inspect.signature(layout.step).parameters
    names = list(sig)
    # the two pools go by the model's own names for what they hold
    assert len(names) >= len(STEP)
    assert names[:1] + names[3:len(STEP)] == list(STEP[:1] + STEP[3:])
    assert [sig[n].default for n in STEP[9:]] == [None, True, None, None]
    # after them: the state's pools and the further pools, where the
    # layout has them, and what a model's own tests ask of its step
    more = names[len(STEP):]
    assert ("state" in more) == bool(layout.state)
    assert ("pools" in more) == bool(layout.more_pools)
    assert all(sig[n].default is not inspect.Parameter.empty for n in more)
    assert layout.step.__module__.startswith("paddle_tpu.models.")
