"""`calibrate_lm.py` for a language-model train cell of the block-diffusion
objective: the same tool, run the same way, with this objective's faults
(`harness/bd_faults.py`) registered beside the other family's, so that
`--faults own_clean_block_visible,one_expert_fewer,not_renormalised` names
them:

    python benchmark/tools/calibrate_lm_bd.py <cell> --seeds 1,2 [--control]
        [--faults own_clean_block_visible,one_expert_fewer,not_renormalised]
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import calibrate_lm

    from benchmark.harness import bd_faults, lm_faults

    lm_faults.FAULTS.update(bd_faults.FAULTS)
    return calibrate_lm.main(argv)


if __name__ == "__main__":
    sys.exit(main())
