"""Faults a language-model train cell whose layers hold a state-space mixer
must be able to see, planted under the runner's tap as `bd_faults.py`'s
are: each is the state-space layer's rule on the doubled row broken in one
place. Never used by a benchmark run.

  - `noised_from_zero`: a noised block's scan starts from a ZERO state
    instead of the clean copy's state at its start;
  - `noised_continues_noised`: the noised copy runs as a row of its own, a
    block's scan continuing the NOISED copy's state;
  - `conv_reads_noised`: a noised position's convolution reads the noised
    copy before its block where it must read the clean one.

Each stands a changed function in `deepof_tpu.ops.ssm`'s place while the
program's own step, built anew, is traced (its first call), and puts the
sound one back after every call, so that no other run of the process sees
it.
"""

from __future__ import annotations

import contextlib


def noised_from_zero(ssm):
    sound = ssm.doubled_scan

    def scan(xn, dtn, bn, cn, xc, dtc, bc, cc, A, chunk, block, dtype):
        yc = sound(xn, dtn, bn, cn, xc, dtc, bc, cc, A, chunk, block, dtype)[1]
        # the clean copy with no input: its state is zero everywhere
        yn = sound(xn, dtn, bn, cn, xc * 0.0, dtc, bc, cc, A, chunk, block,
                   dtype)[0]
        return yn, yc
    return {"doubled_scan": scan}


def noised_continues_noised(ssm):
    sound = ssm.doubled_scan

    def scan(xn, dtn, bn, cn, xc, dtc, bc, cc, A, chunk, block, dtype):
        yc = sound(xn, dtn, bn, cn, xc, dtc, bc, cc, A, chunk, block, dtype)[1]
        # the noised copy in the clean copy's place: its own causal scan
        yn = sound(xn, dtn, bn, cn, xn, dtn, bn, cn, A, chunk, block, dtype)[1]
        return yn, yc
    return {"doubled_scan": scan}


def conv_reads_noised(ssm):
    return {"doubled_conv": lambda xn, xc, w, bias, block:
            ssm.causal_conv(xn, w, bias)}


@contextlib.contextmanager
def planted(which):
    """`deepof_tpu.ops.ssm` with the fault's functions in place."""
    from deepof_tpu.ops import ssm

    changed = which(ssm)
    sound = {name: getattr(ssm, name) for name in changed}
    for name, fn in changed.items():
        setattr(ssm, name, fn)
    try:
        yield
    finally:
        for name, fn in sound.items():
            setattr(ssm, name, fn)


def fault(which):
    def plant(tap, trainer) -> None:
        from deepof_tpu.models.registry import model_for
        from deepof_tpu.train.step import make_train_step

        step = make_train_step(model_for(trainer.cfg), trainer.cfg,
                               trainer.dataset.mean, trainer.mesh)

        def call(state, batch):
            with planted(which):
                return step(state, batch)
        tap.inner = call
    return plant


FAULTS = {"noised_from_zero": fault(noised_from_zero),
          "noised_continues_noised": fault(noised_continues_noised),
          "conv_reads_noised": fault(conv_reads_noised)}
