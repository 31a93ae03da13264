#!/usr/bin/env python3
"""Sound runs and the controls of a ``serve_kimi_linear`` cell in ONE
process, at the cell's own size on the chip: ``tools/controls_mellum2.py``
with this runner's controls (``runners/serve_kimi_linear.py``):

    python3 benchmarks/tools/controls_kimi_linear.py --workload <cell> \
        --seeds 31,32,33,34,35,36 --seconds 20 \
        --controls none,state_bf16,decay_bf16,beta_dropped,fp8,gates_held

``state_bf16`` the KDA state kept in bf16 from token to token,
``decay_bf16`` the decay a channel rounded to bf16, ``beta_dropped`` beta
left out, ``fp8`` the reference with every matmul operand in fp8,
``gates_held`` the gates normalised over the held experts only.  Each has
to come out as not correct.
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.tools import controls_mellum2  # noqa: E402

CONTROLS = {"none": {}, "state_bf16": {"control_state": "bfloat16"},
            "decay_bf16": {"control_decay": "bfloat16"},
            "beta_dropped": {"control_beta": "dropped"},
            "fp8": {"control_lowp": "fp8"},
            "gates_held": {"control_gates": "held"}}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not any(a.startswith("--controls") for a in argv):
        argv += ["--controls", ",".join(CONTROLS)]
    controls_mellum2.CONTROLS = CONTROLS
    return controls_mellum2.main(argv)


if __name__ == "__main__":
    sys.exit(main())
