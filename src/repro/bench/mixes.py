"""The instruction-mix registry — C2 of the paper, declared exactly once.

Arm-membench's central idea is that the *same* data stream measured under
different instruction mixes (LOAD-only / LOAD+FADD / LOAD+NOP) attributes the
bottleneck.  Every mix the repo can run — through the XLA oracles *or* the
Pallas TPU embodiment — is declared here, with its own bytes/flops accounting,
so the two backends can never disagree about what a measurement means.

    mix            ops/element     Armv8 analogue
    ``load_only``  0               pure LD1D loop (Pallas-only: XLA DCE's a
                                   dead load, the Pallas pipeline DMAs the
                                   block into VMEM regardless)
    ``load_sum``   1 add           the FADD accumulation loop
    ``copy``       1 store         STREAM-copy (write path exercised)
    ``triad``      2 flops         STREAM-triad a = b + s*c (2 reads, 1 write)
    ``fma_k``      2k flops        NOP-substitution ladder: k-deep dependent
                                   FMA chain; the knee is the measured ridge
    ``mxu``        2*128 flops     one 128x128 matmul per tile (MXU saturation)
    ``rw_RtoW``    2(R-1) flops    parameterized R:W load/store-ratio family
                                   (store-path attribution): R read streams
                                   combined triad-style, stored to W write
                                   streams — copy == rw_1to1, triad == rw_2to1
    ``latency_chase``  0           dependent-chain pointer walk: every load's
                                   address comes from the previous load, so
                                   time per step is access *latency*, not
                                   bandwidth; the probe half of the
                                   Mess-style loaded-latency composite
                                   (``BenchSpec.load`` adds generators)
    ``all_reduce``     0           collective: every rank's shard summed and
                                   left on every rank (OSU ``osu_allreduce``),
                                   the interconnect analogue of NUMA-remote
                                   access; ``sharded`` backend only

The ``rw_RtoW`` family is generated by one shared formula (``rw_ratio``):
``bytes_per_pass = (R + W) * nbytes`` and ``flops_per_pass = 2(R-1) * n`` —
the (R-1) mul+add pairs that fold R read streams into one value.  The
registry lists a canonical ladder (``RW_RATIOS``); any ``rw_RtoW`` with
R, W in 1..``MAX_RW`` resolves through ``get_mix``, like the fma family.

A collective mix (``all_reduce``) moves bytes that depend on the group
size k, not on per-element constants.  ``sizes`` is the global working set,
and each of the k mesh shards is one rank's message of S = nbytes / k:
``bytes_per_pass(nbytes, k)`` is that payload S (nccl-tests' algbw
numerator) and ``bus_bytes_per_pass(nbytes, k)`` the least any algorithm
sends per rank, ``BUS_FACTORS[op](k) * S`` (the busbw numerator; 2(k-1)/k
for an all-reduce).  It needs a mesh, so it runs on the ``sharded``
backend alone (no alias to the single-device oracles).  ``registry()`` and
``mix_names()`` list the instruction mixes; ``collective_names()`` lists
the collectives, and ``get_mix`` resolves both.

Consumers: ``repro.bench.backends`` (kernel dispatch), ``repro.bench.runner``
(work accounting), ``repro.core.instruction_mix`` (legacy ``mixes()`` view),
``repro.kernels.membench.ops.work_per_call`` (legacy accounting view).

The declared formulas here are *paper-logical* — the traffic the algorithm
needs, the GB/s denominators.  The compiled program may legitimately move
more (materialized partials, interpret-mode block emulation) or, buggily,
less (hoisted or dead-code-eliminated timed work).  ``repro.audit``
statically maps these declarations onto each backend's compiled HLO and
fails loudly on any unexplained gap — edit a formula here and
``python -m repro.bench audit`` (or ``tests/test_audit.py``) is the thing
that catches a typo before it silently rescales every reported number.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

FMA_DEPTHS = (1, 2, 4, 8, 16, 32, 64)

#: canonical registered R:W ladder (reads, writes); get_mix accepts any
#: rw_RtoW with both sides in 1..MAX_RW
RW_RATIOS = ((1, 2), (1, 1), (2, 1), (3, 1), (4, 1))
MAX_RW = 8
#: no leading zeros: a spelling like rw_01to1 must NOT resolve, or the
#: BenchPoint.mix it produces would not match the name the spec asked for
_RW_NAME = re.compile(r"rw_([1-9]\d*)to([1-9]\d*)\Z")

#: the coefficient folding each extra read stream into the store value
#: (v = s0 + c*s1 + ... — triad's scale constant); shared by the xla oracle
#: (core.instruction_mix.k_rw), the Pallas embodiment
#: (kernels.membench._rw_kernel), and the test references
RW_COMBINE_COEF = 1.5

#: full-buffer sweeps each co-scheduled bandwidth generator performs per
#: probe pass in a loaded-latency composite (``BenchSpec.load`` > 0).  A
#: Mess-style generator runs for the *duration* of the probe; on a
#: serialized substrate that duration is emulated by a fixed generator:probe
#: work ratio — one sweep is vectorized and ~100x cheaper than the serial
#: chase walk, so a single sweep per pass would be invisible in the latency
#: signal.  Shared by the xla/pallas composite kernels, the Runner's
#: generator-GB/s accounting, and repro.audit's expected compiled counts —
#: declared ONCE here so they cannot disagree.
GEN_SWEEPS_PER_PASS = 16

#: collective -> bytes each rank must send per byte of its payload in a group
#: of k ranks (nccl-tests ``doc/PERFORMANCE.md``, the bus-bandwidth factor)
BUS_FACTORS = {"all_reduce": lambda k: 2.0 * (k - 1) / k}

# execution aliases: the sharded/distributed backends run the xla oracles per
# shard, so a mix runnable on xla is runnable on both (same kernels, same
# accounting)
_BACKEND_ALIASES = {"sharded": "xla", "distributed": "xla"}


@dataclass(frozen=True)
class MixDef:
    """One instruction mix: name + per-element work accounting + backends."""
    name: str
    flops_per_elem: float          # arithmetic per element per pass
    reads_per_elem: float = 1.0
    writes_per_elem: float = 0.0
    backends: tuple[str, ...] = ("xla", "pallas")
    fma_depth: int = 0             # chain depth for the fma family
    rw: tuple[int, int] | None = None   # (reads, writes) for the rw family
    chase: bool = False            # dependent-chain latency probe?
    collective: str = ""           # the exchange a collective mix times
    description: str = ""

    def bytes_per_pass(self, nbytes: int, devices: int = 1) -> float:
        """Declared bytes of one pass over a working set of ``nbytes``;
        for a collective, the payload of one of ``devices`` ranks."""
        if self.collective:
            return nbytes / devices
        return (self.reads_per_elem + self.writes_per_elem) * nbytes

    def bus_bytes_per_pass(self, nbytes: int, devices: int) -> float:
        """Bytes each rank of a collective sends in one pass, at least."""
        return self.bus_factor(devices) * self.bytes_per_pass(nbytes, devices)

    def bus_factor(self, devices: int) -> float:
        if not self.collective:
            raise ValueError(f"mix {self.name!r} is not a collective")
        return BUS_FACTORS[self.collective](devices)

    def flops_per_pass(self, n_elems: int) -> float:
        return self.flops_per_elem * n_elems

    def supports(self, backend: str) -> bool:
        if self.collective:     # needs the mesh itself, not an oracle alias
            return backend in self.backends
        return _BACKEND_ALIASES.get(backend, backend) in self.backends


def rw_name(reads: int, writes: int) -> str:
    return f"rw_{reads}to{writes}"


def interleavable(mix: "MixDef") -> bool:
    """Does the mix have an independent-chains (``interleave`` > 1) kernel
    variant?  Declared ONCE here — both backends and the spec gating read it
    (the variants live in core.instruction_mix ``k_*_istream`` and the
    membench kernels' chunked bodies)."""
    return mix.name in ("load_sum", "copy") or mix.rw is not None


def rw_ratio(reads: int = 1, writes: int = 1) -> MixDef:
    """The parameterized R:W family — ONE shared accounting formula.

    Per element per pass: R stream reads + W stream writes (so
    ``bytes_per_pass = (R + W) * nbytes``) and the 2(R-1) flops that combine
    the R read streams triad-style (``v = s0 + 1.5*s1 + ... + 1.5*s_{R-1}``)
    before storing ``v`` to each of the W write streams.  The formula
    generalizes the fixed mixes: ``rw_1to1`` accounts exactly like ``copy``
    and ``rw_2to1`` exactly like ``triad``.
    """
    if not (1 <= reads <= MAX_RW and 1 <= writes <= MAX_RW):
        raise ValueError(
            f"rw_ratio needs 1 <= reads, writes <= {MAX_RW} (each stream is "
            f"a full working-set buffer): got ({reads}, {writes})")
    return MixDef(
        rw_name(reads, writes),
        flops_per_elem=2.0 * (reads - 1),
        reads_per_elem=float(reads), writes_per_elem=float(writes),
        rw=(reads, writes),
        description=f"{reads} read : {writes} write streams "
                    f"(store-path attribution, R:W = {reads}:{writes})")


def _build_registry() -> dict[str, MixDef]:
    out = {
        "load_only": MixDef(
            "load_only", 0.0, backends=("pallas",),
            description="pure data movement; one lane feeds the accumulator"),
        "load_sum": MixDef(
            "load_sum", 1.0,
            description="load + accumulate (the FADD loop)"),
        "copy": MixDef(
            "copy", 0.0, reads_per_elem=1.0, writes_per_elem=1.0,
            description="STREAM copy: read stream + write stream"),
        "triad": MixDef(
            "triad", 2.0, reads_per_elem=2.0, writes_per_elem=1.0,
            description="STREAM triad a = b + s*c"),
        "mxu": MixDef(
            "mxu", 2.0 * 128.0,
            description="one (rows,128)@(128,128) matmul per tile"),
        "latency_chase": MixDef(
            "latency_chase", 0.0, reads_per_elem=1.0, writes_per_elem=0.0,
            chase=True,
            description="dependent-chain pointer walk (latency probe): each "
                        "load's address is the previous load's value, so no "
                        "two loads overlap — time/step IS access latency; "
                        "compose with BenchSpec.load bandwidth generators "
                        "for Mess-style loaded-latency curves"),
    }
    for k in FMA_DEPTHS:
        out[f"fma_{k}"] = MixDef(
            f"fma_{k}", 2.0 * k, fma_depth=k,
            description=f"{k}-deep dependent FMA chain per element")
    for r, w in RW_RATIOS:
        out[rw_name(r, w)] = rw_ratio(r, w)
    return out


_REGISTRY: dict[str, MixDef] = _build_registry()

#: the collective mixes, kept apart from the instruction mixes above: their
#: bytes follow the group size, not per-element constants
_COLLECTIVES: dict[str, MixDef] = {
    "all_reduce": MixDef(
        "all_reduce", 0.0, reads_per_elem=0.0, backends=("sharded",),
        collective="all_reduce",
        description="collective: each mesh shard is one rank's message, "
                    "summed over the ranks and left on every rank; bytes "
                    "are the payload a rank (algbw)"),
}


def registry() -> dict[str, MixDef]:
    """name -> MixDef for every instruction mix (shared, do not mutate);
    the collective mixes are ``collective_names()``."""
    return dict(_REGISTRY)


def get_mix(name: str) -> MixDef:
    if name in _REGISTRY:
        return _REGISTRY[name]
    if name in _COLLECTIVES:
        return _COLLECTIVES[name]
    if name.startswith("fma_"):
        # the fma family is open-ended: any positive chain depth is a valid
        # mix (registry() lists only the canonical ladder)
        try:
            k = int(name.split("_", 1)[1])
        except ValueError:
            k = 0
        if k >= 1:
            return MixDef(name, 2.0 * k, fma_depth=k,
                          description=f"{k}-deep dependent FMA chain per element")
    if name.startswith("rw_"):
        # like fma, the rw family is open-ended within 1..MAX_RW per side
        m = _RW_NAME.match(name)
        if m:
            try:
                return rw_ratio(int(m.group(1)), int(m.group(2)))
            except ValueError as e:
                raise KeyError(str(e)) from None
    raise KeyError(f"unknown mix {name!r}; known: "
                   f"{sorted(_REGISTRY) + sorted(_COLLECTIVES)}")


def _sort_key(m: MixDef) -> tuple:
    """Deterministic listing order: families sort by their parameter (fma by
    depth, rw by R:W ratio) inside their alphabetic slot, everything else by
    name — CLI output no longer depends on registration order."""
    if m.rw is not None:
        return ("rw", m.rw[0] / m.rw[1], m.name)
    if m.fma_depth:
        return ("fma", float(m.fma_depth), m.name)
    return (m.name, 0.0, m.name)


def mix_names(backend: str | None = None) -> list[str]:
    """All mix names, optionally only those a given backend supports, in
    deterministic order (sorted by family parameter, then name)."""
    return [m.name for m in sorted(_REGISTRY.values(), key=_sort_key)
            if backend is None or m.supports(backend)]


def collective_names(backend: str | None = None) -> list[str]:
    """The collective mixes, optionally only those ``backend`` runs
    (``mix_names`` lists the instruction mixes)."""
    return [name for name, m in sorted(_COLLECTIVES.items())
            if backend is None or m.supports(backend)]
