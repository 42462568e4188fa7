"""The Pallas backend's default tile (``PallasBackend._resolve``): the
largest power-of-two tile whose double-buffered read and write streams fit
the VMEM budget and leave ``MIN_GRID_STEPS`` grid steps a pass, never
smaller than the floor tile; an explicit ``block_rows`` as given; the
chase's pointer-cycle tile unchanged.  Expected rows are written out, not
recomputed from the rule."""
import jax.numpy as jnp
import pytest

from repro.bench import BenchSpec, Runner
from repro.bench.backends import get_backend
from repro.bench.mixes import get_mix, mix_names
from repro.core.buffers import working_set_shape

GIB, MIB, KIB = 2**30, 2**20, 2**10
#: every Pallas mix but the chase (its tile is the cycle's extent)
STREAMING = [m for m in mix_names("pallas") if not get_mix(m).chase]


def _rows(mix, nbytes, dtype="float32", **knobs):
    spec = BenchSpec(mixes=(mix,), sizes=(nbytes,), dtype=dtype,
                     backend="pallas", **knobs)
    shape = working_set_shape(nbytes, jnp.dtype(dtype))
    return get_backend("pallas")._resolve(spec, get_mix(mix), shape,
                                          jnp.dtype(dtype))


CASES = (
    # 1 GiB f32 is 2,097,152 rows: the budget (8 MiB over 2 buffers a
    # stream) sets the tile
    [("copy", GIB, "float32", {}, 4096),          # 2 streams, 2 MiB tiles
     ("triad", GIB, "float32", {}, 2048),         # 3 streams, 1 MiB tiles
     ("load_sum", GIB, "float32", {}, 8192),      # 1 stream, 4 MiB tiles
     ("rw_2to1", GIB, "float32", {}, 2048),
     ("rw_1to2", GIB, "float32", {}, 2048),
     ("copy", GIB, "bfloat16", {}, 8192),         # 2 MiB of bf16
     ("copy", GIB, "float32", {"streams": 4}, 4096),  # 512 blocks, 4 | 512
     # 3 x 4096 rows: 16 steps cap the tile at 768 rows, so 512 (24
     # blocks, 3 streams of 8)
     ("copy", 6 * MIB, "float32", {"streams": 3}, 512),
     # 16,384 rows in 64 streams: 256 rows (64 blocks), not 1024 (16)
     ("copy", 8 * MIB, "float32", {"streams": 64}, 256),
     # an explicit knob is never adjusted, whatever the budget says
     ("copy", GIB, "float32", {"block_rows": 128}, 128),
     ("triad", GIB, "float32", {"block_rows": 8192}, 8192),
     ("load_sum", MIB, "float32", {"block_rows": 24}, 24),
     # the chase keeps its pointer-cycle tile
     ("latency_chase", GIB, "float32", {}, 128),
     ("latency_chase", 16 * KIB, "float32", {}, 32)]
    # at 1 MiB (16 steps of 128 rows) and 16 KiB (32 rows) every mix keeps
    # the floor tile
    + [(m, MIB, "float32", {}, 128) for m in STREAMING]
    + [(m, 16 * KIB, "float32", {}, 32) for m in STREAMING])


@pytest.mark.parametrize(
    "mix,nbytes,dtype,knobs,rows", CASES,
    ids=[f"{m}-{n}B-{d}-{'-'.join(f'{k}{v}' for k, v in kn.items())}"
         for m, n, d, kn, _ in CASES])
def test_default_tile(mix, nbytes, dtype, knobs, rows):
    assert _rows(mix, nbytes, dtype, **knobs) == rows


@pytest.mark.parametrize("mix,nbytes,knobs,counter", [
    ("copy", 2 * MIB, {}, "pallas_tile_budget"),        # 4096 rows: 256
    ("load_sum", MIB, {}, "pallas_tile_default"),
    ("load_sum", MIB, {"block_rows": 64}, "pallas_tile_explicit"),
])
def test_tile_counters_in_result_obs(mix, nbytes, knobs, counter):
    """``meta["obs"]`` counts, per case built, which rule gave the tile."""
    res = Runner().run(BenchSpec(mixes=(mix,), sizes=(nbytes,),
                                 backend="pallas", reps=1, warmup=1,
                                 passes=1, **knobs))
    counters = res.meta["obs"]["counters"]
    assert counters[counter] == 1
    assert not {"pallas_tile_budget", "pallas_tile_default",
                "pallas_tile_explicit"} - {counter} & set(counters)
    assert res.points[0].block_rows == knobs.get("block_rows")
