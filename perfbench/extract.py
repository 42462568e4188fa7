"""The Pallas kernels that a timed program runs, taken out of that program.

A bandwidth cell's timed call is the program's pass loop, which returns only
its accumulator: a copy's output never leaves the call.  So the check traces
the timed callable on its own arguments, finds every ``pallas_call`` in its
program (inside the jit, the while loop and each unrolled sweep), and runs
that same call, with its own parameters, once over the working set.  What is
compared is the kernel that the window ran, not one built alike: on a TPU it
lowers to the same Mosaic payload (``perfbench/tests/test_extract.py``).
"""
from __future__ import annotations

import jax


def pallas_calls(fn, args) -> list:
    """Every ``pallas_call`` equation of ``fn``'s program on ``args``."""
    found: list = []
    _walk(jax.make_jaxpr(fn)(*args).jaxpr, found)
    return found


def _walk(jaxpr, found: list) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)    # a ClosedJaxpr's Jaxpr
                if hasattr(sub, "eqns"):
                    _walk(sub, found)


def as_function(eqn):
    """The call, with its own parameters, as a jitted function of its
    operands."""
    return jax.jit(lambda *xs: eqn.primitive.bind(*xs, **eqn.params))


def _takes(eqn, args) -> bool:
    return ([(tuple(v.aval.shape), v.aval.dtype) for v in eqn.invars]
            == [(tuple(a.shape), a.dtype) for a in args])


def kernel_outputs(fn, args) -> list:
    """What each Pallas kernel of ``fn``'s program writes when it runs once
    on ``args`` (a scalar where its one output holds one element); ``None``
    for a kernel whose operands are not ``args``, one that does not cover
    the working set."""
    outs = []
    for eqn in pallas_calls(fn, args):
        if not _takes(eqn, args):
            outs.append(None)
            continue
        # every mix the benchmark checks writes one output
        (out,) = jax.block_until_ready(as_function(eqn)(*args))
        outs.append(out.reshape(()) if out.size == 1 else out)
    return outs


def kernels_rel_gap(outputs: list, want) -> float:
    """The widest ``rel_gap`` of the kernels' outputs from ``want``; 1, the
    whole answer, where the program runs no kernel over the working set."""
    from perfbench.harness import rel_gap
    if not outputs or any(o is None for o in outputs):
        return 1.0
    return max(rel_gap(o, want) for o in outputs)
