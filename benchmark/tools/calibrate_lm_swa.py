"""`calibrate_lm.py` for a language-model train cell with sliding-window
layers: the same tool, run the same way, with the window's faults
(`harness/swa_faults.py`) registered beside the expert layer's, so that
`--faults window_layers_causal,half_window,full_layer_rotated` names them:

    python benchmark/tools/calibrate_lm_swa.py <cell> --seeds 1,2 [--control]
        [--faults window_layers_causal,half_window,full_layer_rotated]
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import calibrate_lm

    from benchmark.harness import lm_faults, swa_faults

    lm_faults.FAULTS.update(swa_faults.FAULTS)
    return calibrate_lm.main(argv)


if __name__ == "__main__":
    sys.exit(main())
