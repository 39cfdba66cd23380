"""The plain reference: the generated datasets as numpy columns, with
nothing taken from the program, and the precision in which an operation
kind's ``answer`` computes on them.

The one control, ``bfloat16``, computes the same answers with every
integer column and count held in bfloat16, the step below the exact int32
answers the configuration states (a later change might keep keys in 16
bits to halve the bytes a scan reads). Put in the program's place, it
has to come out not correct.
"""
from __future__ import annotations

import numpy as np

CONTROLS = ("bfloat16",)


class Reference:
    def __init__(self, data: dict, control: str = None):
        """``data``: dataset name -> generated columns."""
        if control not in (None,) + CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        self.data = data
        self.control = control
        self._answers: dict = {}

    def num(self, a):
        """An integer column as the reference reads it."""
        a = np.asarray(a)
        if self.control is None or a.dtype.kind not in "iu" or a.ndim != 1:
            return a
        import ml_dtypes

        return a.astype(ml_dtypes.bfloat16)

    def count(self, n):
        if self.control is None:
            return int(n)
        import ml_dtypes

        return float(np.asarray(float(n), ml_dtypes.bfloat16))

    def answer(self, op, module):
        """``module.answer`` of ``op``, computed once per distinct call."""
        key = (op.kind, op.args)
        if key not in self._answers:
            self._answers[key] = module.answer(self, op.args)
        return self._answers[key]
