"""`calibrate_lm.py` for a language-model train cell of the block-diffusion
objective whose layers hold a state-space mixer: the same tool, run the
same way, with the state-space layer's faults (`harness/ssm_faults.py`)
registered beside the block-diffusion ones, so that
`--faults noised_from_zero,noised_continues_noised,conv_reads_noised`
names them:

    python benchmark/tools/calibrate_lm_ssm.py <cell> --seeds 1,2 [--control]
        [--faults noised_from_zero,noised_continues_noised,conv_reads_noised]
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import calibrate_lm

    from benchmark.harness import bd_faults, lm_faults, ssm_faults

    lm_faults.FAULTS.update(bd_faults.FAULTS)
    lm_faults.FAULTS.update(ssm_faults.FAULTS)
    return calibrate_lm.main(argv)


if __name__ == "__main__":
    sys.exit(main())
