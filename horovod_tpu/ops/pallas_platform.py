"""The one rule for Pallas interpret mode, shared by every kernel here.

A kernel is interpreted only where the call is lowered for the CPU
(the test suite, the virtual-device dryruns); lowered for anything else
it is compiled. jax makes the choice at lowering time from the platform
the computation is being built for — not from whichever backend happens
to be this process's default — so an AOT compile for a TPU topology on
a CPU host gets the compiled kernel, and no probe of the devices exists
that could fail and silently select the interpreter on a chip.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax


def call_by_platform(make_call: Callable[[bool], Callable], *args,
                     interpret: Optional[bool] = None):
    """Run `make_call(interpret)(*args)`.

    `interpret=None` applies the rule above; an explicit bool forces
    that mode on every platform."""
    if interpret is not None:
        return make_call(bool(interpret))(*args)
    return jax.lax.platform_dependent(
        *args, cpu=make_call(True), default=make_call(False))
