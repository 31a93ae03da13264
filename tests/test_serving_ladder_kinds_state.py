"""The ladder of step sizes under two kinds of page (Mellum2), under a
recurrent state beside the pages (Nemotron-H) and under both a third
pool a page and a recurrent state (MiniCPM-SALA, whose layout states a
tile: its ladder has rungs): the same check as
``test_serving_ladder.py``'s, in a file of its own so that another
worker takes these engines' compiles."""

import pytest

from serving_ladder_toys import (  # noqa: F401 - compiles is a fixture
    SALA_SCAN_TILE, check_a_ladder_serves_what_the_top_rung_serves, compiles)


@pytest.mark.parametrize("name", ["mellum2", "nemotron", "minicpm_sala"])
def test_a_ladder_serves_what_the_top_rung_serves(name, compiles,
                                                  monkeypatch):
    if name != "minicpm_sala":
        check_a_ladder_serves_what_the_top_rung_serves(name, compiles)
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
