"""Keep the inputs and outputs of the xla tier's device programs, for the
window calls the check samples.

`KernelTap` puts a thin wrapper in place of the planner's two jitted
programs (`repro.core.xla.kernels._phase2_keys_jit` and `_screen_jit`).
The wrapper calls the program itself, so what runs on the device, its
name in the trace and its compiled cache entry are the program's own.
While `tag` is set it keeps a copy of the call's per-call inputs (host
arrays) and the output arrays as the program returned them; it adds no
read-back and no sync (the program reads the outputs back itself).
Nothing of the instance's own device tensors is kept: the reference
builds its grids from the raw fields (`bench.lib.xla_ref`).
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Per-call arguments of each program, by position after the instance
# tensors it takes first.
PHASE2_ARGS = ("i_idx", "y", "rr", "E", "D", "act_jk", "act_cost", "act_d",
               "act_valid")
SCREEN_ARGS = ("g_i", "g_lane", "z_lt", "act_jk", "act_nm", "act_d",
               "act_ok", "load", "s_g", "s_jk", "dyn", "bound", "rr2",
               "err_num", "del_num", "fthr")
PROGRAMS = {"_phase2_keys_jit": PHASE2_ARGS, "_screen_jit": SCREEN_ARGS}


@dataclasses.dataclass
class TappedCall:
    tag: object             # what the window call set (its pool entry)
    program: str
    args: dict              # per-call inputs, host copies
    out: object             # the program's outputs, as returned


class KernelTap:
    def __init__(self):
        from repro.core.xla import kernels
        self._kernels = kernels
        self.tag = None
        self.calls: list[TappedCall] = []
        self._real = {}
        for name, arg_names in PROGRAMS.items():
            real = getattr(kernels, name)
            real = getattr(real, "bench_tap_real", real)
            self._real[name] = real
            setattr(kernels, name, self._wrap(name, real, arg_names))

    def _wrap(self, name, real, arg_names):
        n = len(arg_names)

        def tapped(*args):
            out = real(*args)
            if self.tag is not None:
                kept = {k: np.array(v) for k, v in zip(arg_names, args[-n:],
                                                       strict=True)}
                self.calls.append(TappedCall(self.tag, name, kept, out))
            return out

        tapped.bench_tap_real = real
        return tapped

    def close(self) -> None:
        """Put the programs back as they were."""
        for name, real in self._real.items():
            setattr(self._kernels, name, real)
        self._real = {}
