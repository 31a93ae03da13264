#!/usr/bin/env python3
"""Sound runs and the controls of a ``serve_minicpm_sala`` cell in ONE
process, at the cell's own size on the chip: ``tools/controls_mellum2.py``
with this runner's controls (``runners/serve_minicpm_sala.py``):

    python3 benchmarks/tools/controls_minicpm_sala.py --workload <cell> \
        --seeds 31,32,33,34,35,36,37,38 --seconds 20 \
        --controls none,own_selection,fp8,dense,no_forced,restore_zeros,no_decay,no_gate

``fp8`` the reference with every matmul operand in fp8, ``dense`` every
row attending its whole context, ``no_forced`` the forced first and
local blocks left out of the selection, ``restore_zeros`` a restore that
starts from zeros instead of the snapshot, ``no_decay`` the decay left
out, ``no_gate`` the attention layers' output gate left out.  Each has
to come out as not correct.  ``own_selection`` is a sound run whose
reference selects for itself at the probed positions too (the reading
beside which the engine's selection was given to it: PERF.md section 2).
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.tools import controls_mellum2  # noqa: E402

CONTROLS = {"none": {}, "own_selection": {"reference_selection": "own"},
            "fp8": {"control_lowp": "fp8"},
            "dense": {"control_attend": "dense"},
            "no_forced": {"control_forced": "dropped"},
            "restore_zeros": {"control_restore": "zeros"},
            "no_decay": {"control_decay": "dropped"},
            "no_gate": {"control_gate": "dropped"}}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not any(a.startswith("--controls") for a in argv):
        argv += ["--controls", ",".join(CONTROLS)]
    controls_mellum2.CONTROLS = CONTROLS
    return controls_mellum2.main(argv)


if __name__ == "__main__":
    sys.exit(main())
