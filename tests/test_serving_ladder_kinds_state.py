"""The ladder of step sizes under two kinds of page (Mellum2) and under
a recurrent state beside the pages (Nemotron-H): the same check as
``test_serving_ladder.py``'s, in a file of its own so that another
worker takes these engines' compiles."""

import pytest

from serving_ladder_toys import (  # noqa: F401 - compiles is a fixture
    check_a_ladder_serves_what_the_top_rung_serves, compiles)


@pytest.mark.parametrize("name", ["mellum2", "nemotron"])
def test_a_ladder_serves_what_the_top_rung_serves(name, compiles):
    check_a_ladder_serves_what_the_top_rung_serves(name, compiles)
