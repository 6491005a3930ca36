"""The pass between the attention's projections and its kernels
(`name="qk_prep_fwd"` / `"qk_prep_bwd"`, one share over both) against its
roofline: the bytes `benchmark/kernels/qk_prep.py` reckons for the calls
the configuration's `step_kernels` lists, at the HBM's rate. None where
the step holds no such kernel (the layers' own elementwise code)."""

from benchmark.kernels.named_share import read_pairs


def read(obs):
    return read_pairs(obs, [("qk_prep.forward", "%qk_prep_fwd"),
                            ("qk_prep.backward", "%qk_prep_bwd")])
