"""Pluggable benchmark backends: the XLA oracles, the Pallas embodiment, and
the sharded / distributed multi-device backends (the paper's Figure-4
core-scaling study, single-process and multi-process respectively).

A Backend turns (BenchSpec, mix, working set, passes) into a zero-arg callable
whose return value is the serialization point for timing.  Work accounting is
NOT a backend concern — the Runner reads it from the shared mix registry, so
all backends report identical bytes/flops for the same spec by construction.

The built-in backends split ``build`` into two halves so the Runner can cache
the expensive one:

    make_case(spec, mix, shape, dtype, passes)   the compiled callable —
        a pure function of the knobs and the buffer *shape*, never closing
        over a buffer.  The Runner caches these by key (see ``case_key``),
        so knob sweeps (``run_many``) and ``compare`` stop re-tracing
        identical kernels, and a cached case can never retain a working set.
    bind_case(case, spec, mix, x)                per-buffer binding —
        closes over the actual working set (plus any companion buffers,
        e.g. triad's second read stream) and is rebuilt per size, then
        dropped with the buffer.

Third-party backends only need ``build`` (the original protocol); the Runner
falls back to it, uncached, when ``make_case`` is absent.
"""
from __future__ import annotations

import functools
from typing import Callable, Protocol, runtime_checkable

import jax.numpy as jnp

from repro.bench.mixes import MixDef, get_mix, interleavable
from repro.bench.spec import BenchSpec, BenchSpecError, knob_names
from repro.obs import metrics, trace


#: BenchSpec fields that can NEVER change what make_case compiles — either
#: they are explicit slots of the cache key already (mixes/sizes/dtype/
#: backend/passes resolve to the per-case key columns) or they only shape
#: the measurement around the compiled case (repetition discipline, buffer
#: fill value, labels).  Everything else — including any FUTURE knob — is
#: part of the key by default: forgetting to classify a new field makes the
#: cache miss, never alias.
_NON_CASE_FIELDS = frozenset({
    "mixes", "sizes", "dtype", "backend", "passes",     # explicit key slots
    "reps", "warmup", "value", "target_bytes", "tags",  # measurement-only
})


def case_knobs(spec: BenchSpec) -> tuple:
    """(name, value) pairs of every spec field that can affect compilation,
    derived from the dataclass fields (not an explicit list) so new knobs
    are cache-safe by construction.  Shared by ``case_key`` and the istream
    profile cache."""
    import dataclasses
    return tuple((f.name, getattr(spec, f.name))
                 for f in dataclasses.fields(spec)
                 if f.name not in _NON_CASE_FIELDS)


def _dispatched(case: Callable, backend: str, mix: str) -> Callable:
    """``case`` behind the ``case.dispatch`` span, which covers each call
    from Python entry to the enqueue of its program (the caller's
    ``block_until_ready`` lies outside).  With tracing off it calls the case
    directly and never touches ``span()``.  It keeps the case's name, so
    ``jax.jit`` of it names the module as the case does."""
    tracer = trace.get_tracer()

    @functools.wraps(case)
    def dispatch(*bufs):
        if not tracer.enabled:
            return case(*bufs)
        with tracer.span("case.dispatch", backend=backend, mix=mix):
            return case(*bufs)
    return dispatch


def _gate(backend_name: str, rule: str) -> str:
    """Suffix naming the backend gate that rejected a knob combination, plus
    the valid knob names — so the error decodes without opening spec.py."""
    return (f" [gate: {rule}, raised by {backend_name}.validate; valid spec "
            f"knobs: {', '.join(knob_names())}]")


@runtime_checkable
class Backend(Protocol):
    """One way of executing a mix on a device."""
    name: str

    def supports(self, mix: MixDef) -> bool:
        """Can this backend run the mix at all (knobs aside)?"""
        ...

    def validate(self, spec: BenchSpec) -> None:
        """Raise BenchSpecError for knob combinations this backend can't run."""
        ...

    def build(self, spec: BenchSpec, mix: MixDef, x, passes: int
              ) -> Callable[[], object]:
        """Zero-arg callable running `passes` passes of `mix` over `x`; the
        returned jax array is the block_until_ready serialization point."""
        ...


class _CaseBackend:
    """Shared make_case/bind_case machinery for the built-in backends."""
    multi_device = False     # True: accepts BenchSpec(devices > 1)

    def case_key(self, spec: BenchSpec, mix: MixDef, shape, dtype,
                 passes: int) -> tuple:
        """Everything ``make_case`` depends on — the Runner's cache key.
        The knob columns derive from the FULL spec field set minus the
        measurement-only fields (``case_knobs``), so a future knob that
        changes compilation can never alias a stale cached case."""
        return (self.name, mix.name, tuple(shape), str(dtype), passes,
                case_knobs(spec))

    def make_case(self, spec: BenchSpec, mix: MixDef, shape, dtype,
                  passes: int) -> Callable:
        raise NotImplementedError

    def prepare_buffer(self, spec: BenchSpec, x):
        """Per-size buffer placement hook, called once before binding that
        size's cases (e.g. the sharded backend spreads x over its mesh here
        so per-mix bindings share one placed copy)."""
        return x

    def abstract_args(self, spec: BenchSpec, mix: MixDef, shape, dtype
                      ) -> tuple:
        """ShapeDtypeStructs matching ``make_case``'s positional buffers —
        what ``jax.jit(case).lower(...)`` needs (the istream extractor
        lowers cached cases without materializing working sets)."""
        import jax
        sds = jax.ShapeDtypeStruct(tuple(shape), dtype)
        if mix.chase:
            perm = jax.ShapeDtypeStruct(tuple(shape), jnp.int32)
            return (perm, sds) if spec.load else (perm,)
        return (sds,) * _mix_arity(mix)

    def bind_case(self, case: Callable, spec: BenchSpec, mix: MixDef, x
                  ) -> Callable[[], object]:
        return lambda: case(x)

    def build(self, spec, mix, x, passes):
        case = self.make_case(spec, mix, x.shape, x.dtype, passes)
        return self.bind_case(case, spec, mix, self.prepare_buffer(spec, x))


def _validate_oracle_knobs(spec: BenchSpec, backend_name: str) -> None:
    """Knob rules of the core.instruction_mix oracles (shared by the xla
    backend and the sharded backend, which runs the same kernels per shard).
    A collective mix that the backend supports has rules of its own
    (``_validate_collective_knobs``)."""
    for m in spec.mixes:
        mix = get_mix(m)
        if mix.collective and mix.supports(backend_name):
            _validate_collective_knobs(spec, mix, backend_name)
            continue
        if "xla" not in mix.backends:
            raise BenchSpecError(f"mix {m!r} not supported on {backend_name}"
                                 + _gate(backend_name, "mix support"))
        if spec.streams > 1 and m != "load_sum":
            raise BenchSpecError(
                f"{backend_name} backend expresses streams>1 only for "
                f"load_sum (the strided-walk oracle); got mix {m!r}"
                + _gate(backend_name, "streams>1 needs the strided oracle"))
        if spec.block_rows is not None and m != "load_sum":
            raise BenchSpecError(
                f"{backend_name} backend expresses block_rows only for "
                f"load_sum (the blocked-walk oracle); got mix {m!r}"
                + _gate(backend_name, "block_rows needs the blocked oracle"))
        if spec.interleave > 1 and not interleavable(mix):
            raise BenchSpecError(
                f"mix {m!r} has no interleaved variant on {backend_name} "
                f"(interleave>1 needs independent per-chunk chains — "
                f"load_sum, copy, or the rw_RtoW family)"
                + _gate(backend_name, "interleave>1 needs an interleavable "
                                      "mix"))
    if spec.streams > 1 and spec.block_rows is not None:
        raise BenchSpecError(f"{backend_name} backend: streams and "
                             "block_rows are mutually exclusive knobs"
                             + _gate(backend_name,
                                     "streams xor block_rows"))
    if spec.interleave > 1 and (spec.streams > 1
                                or spec.block_rows is not None):
        raise BenchSpecError(
            f"{backend_name} backend: interleave>1 does not compose with "
            f"streams>1 or block_rows (the interleaved oracles walk the "
            f"whole buffer in row chunks)"
            + _gate(backend_name, "interleave xor streams/block_rows"))


def _validate_collective_knobs(spec: BenchSpec, mix: MixDef,
                               backend_name: str) -> None:
    """A collective exchanges each rank's whole shard: the oracles' walk
    knobs (streams, block_rows, interleave) have no meaning for it."""
    for knob, default in (("streams", 1), ("block_rows", None),
                          ("interleave", 1)):
        if getattr(spec, knob) != default:
            raise BenchSpecError(
                f"collective mix {mix.name!r} exchanges whole shards: "
                f"{knob}={getattr(spec, knob)} has no meaning for it"
                + _gate(backend_name, "collectives take no walk knobs"))


def _mix_arity(mix: MixDef, load: int = 0) -> int:
    """Positional buffer count of a mix's oracle case (reads then writes).
    A chase probe takes its permutation buffer, plus the generator working
    set when ``load`` generators are composed in."""
    if mix.chase:
        return 2 if load else 1
    if mix.name == "triad":
        return 3
    if mix.rw is not None:
        return mix.rw[0] + mix.rw[1]
    return 1


def _mix_operands(mix: MixDef, x, place=lambda a: a, load: int = 0,
                  parts: int = 1) -> tuple:
    """Every buffer a mix's oracle case consumes, in positional order, built
    OUTSIDE the timed call.  ``x`` passes through as-is (the Runner already
    placed it via prepare_buffer); companion streams — triad's (a, c), the rw
    family's extra read and write streams, the chase probe's permutation
    buffer (``parts`` local cycles: one per mesh shard) — go through
    ``place`` (identity on xla, a mesh device_put on sharded)."""
    if mix.chase:
        from repro.core.instruction_mix import chase_perm
        perm = place(jnp.asarray(chase_perm(x.shape, parts)))
        return (perm, x) if load else (perm,)
    if mix.name == "triad":
        return (place(jnp.zeros_like(x)), x, place(x * 0.5))
    if mix.rw is not None:
        from repro.core.instruction_mix import rw_streams
        reads, writes = mix.rw
        # the W write-seed slots only supply shape/dtype — k_rw overwrites
        # every output before reading it — so alias x rather than allocating
        # W zero buffers (peak footprint stays one working set + companions)
        return ((x,)
                + tuple(place(s) for s in rw_streams(x, reads)[1:])
                + (x,) * writes)
    return (x,)


def _oracle_case(spec: BenchSpec, mix: MixDef, rows: int, passes: int,
                 backend_name: str) -> Callable:
    """The per-shape oracle kernel for a mix (pure function of its inputs;
    triad takes (a, b, c), rw_RtoW takes its R+W stream buffers, everything
    else takes x)."""
    from repro.core import instruction_mix as im
    unroll, interleave = spec.unroll, spec.interleave
    if passes % unroll:
        # the Runner rounds auto-picked passes up; a direct build() with
        # explicit passes surfaces here instead of a trace-time ValueError
        raise BenchSpecError(
            f"passes={passes} is not a multiple of unroll={unroll}"
            + _gate(backend_name, "passes % unroll == 0"))
    if interleave > 1 and rows % interleave:
        raise BenchSpecError(
            f"interleave {interleave} does not divide {rows} rows"
            + ("" if backend_name == "xla" else
               f" (the per-device shard on {backend_name})")
            + _gate(backend_name, "interleave | rows"))
    if mix.name == "load_sum" and spec.streams > 1:
        streams = spec.streams
        return lambda x: im.k_strided_sum(x, streams, passes, unroll)
    if mix.name == "load_sum" and spec.block_rows is not None:
        brows = spec.block_rows
        if rows % brows:
            raise BenchSpecError(
                f"block_rows {brows} does not divide {rows} rows"
                + ("" if backend_name == "xla" else
                   f" (the per-device shard on {backend_name})"))
        return lambda x: im.k_blocked_sum(x, brows, passes, unroll)
    if mix.chase:
        load = spec.load
        if load:
            # the single-device composite: probe + generators time-shared in
            # one timed computation (the mesh backends build their own
            # probe-on-shard-0 composite in make_case instead)
            return lambda perm, gen: im.k_chase_loaded(perm, gen, passes,
                                                       unroll, load=load)
        return lambda perm: im.k_chase(perm, passes, unroll)
    if mix.name == "triad":
        return lambda a, b, c: im.k_triad(a, b, c, passes, unroll)
    if mix.rw is not None:
        reads = mix.rw[0]
        if interleave > 1:
            return lambda *bufs: im.k_rw_istream(
                bufs[:reads], bufs[reads:], passes, unroll, interleave)
        return lambda *bufs: im.k_rw(bufs[:reads], bufs[reads:], passes,
                                     unroll)
    name = mix.name
    return lambda x: im.run_mix(name, x, passes, unroll=unroll,
                                interleave=interleave)


def _bind_oracle_case(case: Callable, mix: MixDef, x, load: int = 0
                      ) -> Callable[[], object]:
    """Close an oracle case over its buffers; companion streams are built
    here, outside the timed call (shared by xla and sharded)."""
    bufs = _mix_operands(mix, x, load=load)
    return lambda: case(*bufs)


class XLABackend(_CaseBackend):
    """The jnp oracles from core.instruction_mix (host-measurable)."""
    name = "xla"

    def supports(self, mix: MixDef) -> bool:
        return self.name in mix.backends

    def validate(self, spec: BenchSpec) -> None:
        _validate_oracle_knobs(spec, self.name)

    def make_case(self, spec, mix, shape, dtype, passes):
        return _dispatched(_oracle_case(spec, mix, shape[0], passes,
                                        self.name), self.name, mix.name)

    def bind_case(self, case, spec, mix, x):
        return _bind_oracle_case(case, mix, x, load=spec.load)


class _MeshOracleBackend(_CaseBackend):
    """Shared machinery for backends that run the instruction-mix oracles
    per shard of a 1-D device mesh (``sharded`` on local devices,
    ``distributed`` on the global devices of a multi-process run).

    Subclasses choose the device pool (``_mesh_devices``) and how a host
    buffer becomes a mesh-placed array (``_place``); ``make_case`` — the
    shard_map wrapping of the *same* oracle kernels the xla backend runs —
    is identical for both, so bytes/flops accounting parity across xla /
    sharded / distributed holds by construction (the Runner reads accounting
    from the shared mix registry, never from the backend).
    """
    multi_device = True

    def __init__(self):
        self._meshes: dict[int, object] = {}

    def supports(self, mix: MixDef) -> bool:
        # mixes._BACKEND_ALIASES maps sharded/distributed -> xla (single
        # source of truth for which mixes the oracles implement)
        return mix.supports(self.name)

    def _mesh_devices(self) -> list:
        """The device pool the 1-D mesh draws from (first k are used)."""
        import jax
        return jax.devices()

    def _mesh(self, k: int):
        mesh = self._meshes.get(k)
        if mesh is None:
            import numpy as np
            from jax.sharding import Mesh
            devs = self._mesh_devices()
            if k > len(devs):
                raise BenchSpecError(
                    f"devices={k} exceeds the {len(devs)} visible device(s); "
                    "force host devices with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N")
            mesh = Mesh(np.array(devs[:k]).reshape(k), ("d",))
            self._meshes[k] = mesh
        return mesh

    def validate(self, spec: BenchSpec) -> None:
        _validate_oracle_knobs(spec, self.name)
        if spec.load and spec.devices != spec.load + 1:
            raise BenchSpecError(
                f"{self.name} backend places the latency probe on shard 0 "
                f"and each of the {spec.load} generator(s) on its own "
                f"sibling shard: need devices == load + 1 "
                f"({spec.load + 1}), got devices={spec.devices}"
                + _gate(self.name, "devices == load + 1"))
        self._mesh(spec.devices)        # device-count check

    def make_case(self, spec, mix, shape, dtype, passes):
        import jax
        if mix.collective:
            return _dispatched(self.collective_case(spec, mix, shape, passes),
                               self.name, mix.name)
        per_shard = self.per_shard_case(spec, mix, shape, dtype, passes)
        return _dispatched(jax.jit(lambda *xs: per_shard(*xs).sum()),
                           self.name, mix.name)

    def collective_case(self, spec, mix, shape, passes):
        """A collective mix's timed program: ``passes`` exchanges of every
        rank's shard a call (``core.collective_bench.make_passloop``),
        returning the ``(devices,)`` vector of per-rank accumulators: no
        cross-rank sum after the loop, so the exchanges are the call's
        only collectives."""
        from repro.core.collective_bench import make_passloop
        k = spec.devices
        if shape[0] % k:
            raise BenchSpecError(
                f"devices={k} does not divide the {shape[0]}-row working set")
        if passes % spec.unroll:
            raise BenchSpecError(
                f"passes={passes} is not a multiple of unroll={spec.unroll}"
                + _gate(self.name, "passes % unroll == 0"))
        return make_passloop(self._mesh(k), mix.collective, passes,
                             spec.unroll)

    def per_shard_case(self, spec, mix, shape, dtype, passes):
        """The mesh computation before its cross-shard sum: returns the
        ``(devices,)`` vector of per-shard accumulators, which a check can
        hold against the single-device oracle on each shard's slice."""
        import jax
        from jax.sharding import PartitionSpec as P
        k = spec.devices
        rows, lanes = shape
        if rows % k:
            raise BenchSpecError(
                f"devices={k} does not divide the {rows}-row working set")
        mesh = self._mesh(k)
        n_args = _mix_arity(mix, spec.load)   # triad: (a,b,c); rw: R+W

        if mix.chase and spec.load:
            # the mesh composite: ONE timed computation in which shard 0
            # walks its pointer cycle (the probe) while every sibling shard
            # runs load_sum sweeps over its slice of the generator buffer
            # (the bandwidth generators) — real spatial co-scheduling, not
            # the single-device time-shared emulation
            from repro.bench.mixes import GEN_SWEEPS_PER_PASS
            from repro.core import instruction_mix as im
            if passes % spec.unroll:
                raise BenchSpecError(
                    f"passes={passes} is not a multiple of "
                    f"unroll={spec.unroll}"
                    + _gate(self.name, "passes % unroll == 0"))
            gen_passes = passes * GEN_SWEEPS_PER_PASS
            unroll = spec.unroll

            def body(perm_v, gen_v):     # each v: (1, rows // k, lanes)
                out = jax.lax.cond(
                    jax.lax.axis_index("d") == 0,
                    lambda: im.k_chase(perm_v[0], passes, unroll),
                    lambda: im.k_load_sum(gen_v[0], gen_passes))
                return out.reshape(1)
        else:
            shard = _oracle_case(spec, mix, rows // k, passes, self.name)

            def body(*vs):               # each v: (1, rows // k, lanes)
                return shard(*(v[0] for v in vs)).reshape(1)

        smap = jax.shard_map(body, mesh=mesh,
                             in_specs=(P("d", None, None),) * n_args,
                             out_specs=P("d"), check_vma=False)

        @jax.jit
        def fn(*xs):
            return smap(*(x.reshape(k, rows // k, lanes) for x in xs))

        return fn

    def _sharding(self, k: int):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self._mesh(k), P("d", None))

    def _place(self, a, sharding):
        import jax
        return jax.device_put(a, sharding)

    def prepare_buffer(self, spec, x):
        """One mesh placement per size — every mix's binding shares it."""
        return self._place(x, self._sharding(spec.devices))

    def bind_case(self, case, spec, mix, x):
        # companions live outside the timed call, placed like x (which
        # prepare_buffer already spread across the mesh)
        sharding = self._sharding(spec.devices)
        bufs = _mix_operands(mix, x,
                             place=lambda a: self._place(a, sharding),
                             load=spec.load, parts=spec.devices)
        return lambda: case(*bufs)


class ShardedBackend(_MeshOracleBackend):
    """The working set spread over the first k devices of a 1-D mesh.

    Reproduces the paper's Figure-4 core-count scaling study (aggregate
    bandwidth vs cores until the HBM2 interface saturates): each device runs
    the *same* instruction-mix oracle the xla backend runs, over its shard,
    via ``shard_map`` — so every mix that runs on ``xla`` runs sharded, with
    identical bytes/flops accounting by construction (the Runner reads both
    from the shared registry).  ``BenchSpec(devices=k)`` picks the mesh size;
    at ``devices=1`` this degenerates to the xla backend plus mesh overhead.
    """
    name = "sharded"


class DistributedBackend(_MeshOracleBackend):
    """The sharded oracle-per-shard machinery over the **global** devices of
    a multi-process run (``jax.distributed``) — the paper's Fig-4 scaling
    study taken past one host.

    The ``devices`` knob is unchanged: it counts *global* mesh devices, so a
    spec that ran ``sharded`` on one 8-device host runs ``distributed`` on
    two 4-device hosts byte-for-byte (same accounting, same per-shard
    kernels; ``tests/test_bench_distributed.py`` enforces the parity).  Two
    things differ from ``sharded``:

    * buffer placement: a host-built working set becomes a *global* array
      via ``jax.make_array_from_callback`` — each process materializes only
      its addressable shards on device (``device_put`` can't target
      non-addressable shards).  Companions computed
      *from* the placed buffer (triad's ``x * 0.5``, the rw streams) are
      already global and pass through untouched.
    * process roles: every process runs the identical SPMD measurement loop
      (the trailing cross-shard ``.sum()`` in the compiled case is the
      global serialization point each rep); afterwards
      ``bench.distributed.gather_result`` merges the per-process timings
      into one BenchResult on all processes and process 0 saves it.

    Initialization (``bench.distributed.ensure_initialized``) must happen
    before the jax backend comes up — the CLI's ``run``/``launch`` and
    ``benchmarks/fig4_scaling.py --distributed`` do this for you.  In a
    single-process context this backend degenerates to ``sharded`` exactly.
    """
    name = "distributed"

    def _mesh_devices(self) -> list:
        """Global devices, round-robin across processes — ``devices=k``
        spreads the mesh as evenly as the process topology allows (k=2 on
        2x2 hosts is one device per host, not two on host 0), so a Fig-4
        sweep over intermediate counts exercises the interconnect instead
        of a single host's slice of it."""
        import jax
        devs = jax.devices()
        if jax.process_count() == 1:
            return devs
        by_proc: dict[int, list] = {}
        for d in devs:
            by_proc.setdefault(d.process_index, []).append(d)
        pools = [by_proc[p] for p in sorted(by_proc)]
        return [pool[i] for i in range(max(len(p) for p in pools))
                for pool in pools if i < len(pool)]

    def validate(self, spec: BenchSpec) -> None:
        super().validate(spec)
        import jax
        if jax.process_count() > 1:
            # SPMD needs every process inside the mesh: a process owning no
            # shard has no addressable data and can't even represent the
            # computation — fail with the fix, not an IndexError deep in
            # placement
            covered = {d.process_index
                       for d in self._mesh_devices()[:spec.devices]}
            missing = sorted(set(range(jax.process_count())) - covered)
            if missing:
                raise BenchSpecError(
                    f"devices={spec.devices} leaves process(es) {missing} "
                    f"with no mesh shard; use devices >= one per process "
                    f"or launch fewer processes")

    def _place(self, a, sharding):
        import jax
        if isinstance(a, jax.Array) and not a.is_fully_addressable:
            return a        # already a global array living on the mesh
        if jax.process_count() == 1:
            return jax.device_put(a, sharding)
        import numpy as np
        host = np.asarray(a)
        return jax.make_array_from_callback(host.shape, sharding,
                                            lambda idx: host[idx])


class PallasBackend(_CaseBackend):
    """The Pallas TPU kernels (kernels/membench) with explicit VMEM tiling.

    The platform decides interpret mode (``membench.resolve_interpret``):
    interpreted on the CPU, where only kernel-body semantics are meaningful,
    and compiled on a TPU.
    """
    name = "pallas"

    def supports(self, mix: MixDef) -> bool:
        return self.name in mix.backends

    def _resolve(self, spec: BenchSpec, mix: MixDef, shape, dtype) -> int:
        """The tile's rows: an explicit ``block_rows`` as given; the chase's
        tile, which is the extent of its pointer cycle and no transfer
        unit, by ``floor_block_rows``; every other mix by the VMEM budget
        of its double-buffered read and write streams
        (``default_block_rows``)."""
        from repro.kernels.membench import membench as mb
        if spec.block_rows is not None:
            return spec.block_rows       # explicit knob: never adjusted
        if mix.chase:
            return mb.floor_block_rows(shape[0])
        return mb.default_block_rows(
            shape[0], jnp.dtype(dtype).itemsize,
            int(mix.reads_per_elem + mix.writes_per_elem), spec.streams)

    def validate(self, spec: BenchSpec) -> None:
        from repro.kernels.membench.membench import resolve_interpret
        compiled = not resolve_interpret()
        for m in spec.mixes:
            mix = get_mix(m)
            if not self.supports(mix):
                raise BenchSpecError(f"mix {m!r} not supported on pallas"
                                     + _gate(self.name, "mix support"))
            if mix.chase and compiled:
                raise BenchSpecError(
                    f"mix {m!r} has no compiled Pallas kernel: Mosaic cannot "
                    f"lower the chase's dynamic_slice (R2 in ROADMAP.md); "
                    f"run it on the xla backend"
                    + _gate(self.name, "the chase kernel runs interpreted "
                                       "only"))
            if spec.interleave > 1 and not interleavable(mix):
                raise BenchSpecError(
                    f"mix {m!r} has no interleaved variant on pallas "
                    f"(interleave>1 needs independent per-chunk chains — "
                    f"load_sum, copy, or the rw_RtoW family)"
                    + _gate(self.name, "interleave>1 needs an "
                                       "interleavable mix"))

    def make_case(self, spec, mix, shape, dtype, passes):
        from repro.kernels.membench import ops as mb_ops
        from repro.kernels.membench.membench import (floor_block_rows,
                                                     resolve_interpret)
        rows = self._resolve(spec, mix, shape, dtype)
        metrics.REGISTRY.inc(
            "pallas_tile_explicit" if spec.block_rows is not None
            else "pallas_tile_budget" if rows > floor_block_rows(shape[0])
            else "pallas_tile_default")
        if rows > shape[0] or shape[0] % rows:
            raise BenchSpecError(
                f"block_rows {rows} does not divide {shape[0]} rows")
        n_blocks = shape[0] // rows
        if n_blocks % spec.streams:
            raise BenchSpecError(
                f"streams {spec.streams} does not divide {n_blocks} blocks")
        if passes % spec.unroll:
            raise BenchSpecError(
                f"passes={passes} is not a multiple of unroll={spec.unroll}"
                + _gate(self.name, "passes % unroll == 0"))
        if spec.interleave > 1 and rows % spec.interleave:
            raise BenchSpecError(
                f"interleave {spec.interleave} does not divide the "
                f"{rows}-row VMEM tile"
                + _gate(self.name, "interleave | block_rows"))
        return _dispatched(mb_ops.make_timed_kernel(
            mix.name, depth=mix.fma_depth or 8, block_rows=rows,
            streams=spec.streams, interpret=resolve_interpret(),
            passes=passes, unroll=spec.unroll, interleave=spec.interleave,
            load=spec.load), self.name, mix.name)

    def abstract_args(self, spec, mix, shape, dtype):
        import jax
        sds = jax.ShapeDtypeStruct(tuple(shape), dtype)
        if mix.chase:
            perm = jax.ShapeDtypeStruct(tuple(shape), jnp.int32)
            return (perm, sds) if spec.load else (perm,)
        if mix.name == "triad":
            return (sds, sds)           # fn(x, y)
        if mix.rw is not None:
            return (sds,) * mix.rw[0]   # fn(x, *extra_read_streams)
        return (sds,)

    def bind_case(self, case, spec, mix, x):
        if mix.chase:
            # one pointer cycle per VMEM tile: the grid walks the tiles, the
            # kernel chases the current tile's TILE-LOCAL cycle
            from repro.core.instruction_mix import chase_perm
            rows = self._resolve(spec, mix, x.shape, x.dtype)
            perm = jnp.asarray(chase_perm(x.shape, x.shape[0] // rows))
            if spec.load:
                return lambda: case(perm, x)
            return lambda: case(perm)
        if mix.name == "triad":
            y = x * 0.5
            return lambda: case(x, y)
        if mix.rw is not None:
            # the Pallas embodiment allocates its W outputs via out_shape;
            # only the R read streams are bound (outside the timed call)
            from repro.core.instruction_mix import rw_streams
            bufs = rw_streams(x, mix.rw[0])
            return lambda: case(*bufs)
        return lambda: case(x)


_BACKENDS: dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    _BACKENDS[backend.name] = backend
    return backend


register_backend(XLABackend())
register_backend(ShardedBackend())
register_backend(DistributedBackend())
register_backend(PallasBackend())


def get_backend(name: str) -> Backend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: {sorted(_BACKENDS)}"
        ) from None


def available_backends() -> list[str]:
    return sorted(_BACKENDS)
