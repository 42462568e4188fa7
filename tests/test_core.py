"""Core membench: buffer discipline (hypothesis), timing, sweep, analysis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import analysis, buffers, instruction_mix, sweep, timing
from repro.core.machine_model import TPU_V5E, HardwareSpec, MemLevel, detect_host

# ---------------------------------------------------------------------------
# buffer init — the paper's denormal-avoiding discipline (property-based)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.floats(min_value=1e-3, max_value=1e3, allow_nan=False),
       st.integers(min_value=4, max_value=4096))
def test_init_pattern_no_denormals(value, n):
    arr = buffers.init_pattern(n, value, jnp.float32)
    a = np.asarray(arr)
    assert np.all(np.isfinite(a))
    assert not buffers.has_denormals(a)
    # the (v, 1/v, -v, -1/v) cycle
    np.testing.assert_allclose(a[0], value, rtol=1e-6)
    if n >= 4:
        np.testing.assert_allclose(a[1], 1.0 / value, rtol=1e-6)
        np.testing.assert_allclose(a[2], -value, rtol=1e-6)
        np.testing.assert_allclose(a[3], -1.0 / value, rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=2**12, max_value=2**22))
def test_working_set_size(nbytes):
    x = buffers.working_set(nbytes)
    real = x.size * x.dtype.itemsize
    assert abs(real - nbytes) / nbytes < 0.3 or real >= 8 * 128 * 4
    assert x.shape[1] == 128 and x.shape[0] % 8 == 0


def _host_pattern(n, value, dtype):
    """The host construction, kept as the reference: the float64 cycle
    tiled to n on the host, then cast whole."""
    cycle = np.array([value, 1.0 / value, -value, -1.0 / value], np.float64)
    return np.asarray(jnp.asarray(np.tile(cycle, n // 4 + 1)[:n], dtype=dtype))


def _host_working_set(nbytes, dtype, value, lanes):
    rows, lanes = buffers.working_set_shape(nbytes, dtype, lanes)
    n = rows * lanes
    if jnp.issubdtype(dtype, jnp.integer):
        cycle = np.array([1, 7, -1, -7], dtype=np.int64)
        buf = np.tile(cycle, n // 4 + 1)[:n].astype(np.dtype(dtype))
        return np.asarray(jnp.asarray(buf)).reshape(rows, lanes)
    return _host_pattern(n, value, dtype).reshape(rows, lanes)


@pytest.mark.parametrize("value", [1.234567, 2.0, 1.3579])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16,
                                   jnp.int32])
def test_device_pattern_bit_identical_to_host_construction(dtype, value):
    """The pattern filled on the device holds the very bytes of the host
    construction: for lengths that are not multiples of 4 and for
    (rows, lanes) buffers whose lanes are not either."""
    dtype = jnp.dtype(dtype)
    built = []
    for n in (1, 6, 4099):
        got = np.asarray(buffers.init_pattern(n, value, dtype))
        want = _host_pattern(n, value, dtype)
        assert (got.dtype, got.shape) == (want.dtype, want.shape)
        assert got.tobytes() == want.tobytes(), n
        built.append(got)
    for nbytes, lanes in ((32 * 1024, 128), (5000, 6)):
        got = np.asarray(buffers.working_set(nbytes, dtype, value, lanes))
        want = _host_working_set(nbytes, dtype, value, lanes)
        assert (got.dtype, got.shape) == (want.dtype, want.shape)
        assert got.tobytes() == want.tobytes(), (nbytes, lanes)
        built.append(got)
    if not jnp.issubdtype(dtype, jnp.integer):
        assert not any(buffers.has_denormals(a) for a in built)


def test_pattern_fill_compiles_once_with_no_temporary():
    """One compile per shape and dtype serves every value (the cycle is a
    traced argument), and the fill allocates nothing but its output: one
    working set at a time, as the Runner's peak gauge assumes."""
    lanes = 24      # a shape no other test fills
    before = buffers._fill._cache_size()
    for value in (1.5, 2.0, 3.25, -0.75):
        buffers.working_set(48 * 1024, jnp.float32, value, lanes)
    assert buffers._fill._cache_size() == before + 1
    shape = buffers.working_set_shape(48 * 1024, jnp.float32, lanes)
    ma = buffers._fill.lower(jax.ShapeDtypeStruct((4,), jnp.float32),
                             shape).compile().memory_analysis()
    assert ma.output_size_in_bytes == 48 * 1024
    assert ma.temp_size_in_bytes <= ma.output_size_in_bytes // 64


def test_init_rejects_bad_values():
    with pytest.raises(ValueError):
        buffers.init_pattern(16, 0.0)
    with pytest.raises(ValueError):
        buffers.init_pattern(16, float("inf"))


# ---------------------------------------------------------------------------
# timing harness — cumulative-mean discipline
# ---------------------------------------------------------------------------

def test_timing_harness():
    x = buffers.working_set(64 * 1024)
    t = timing.time_fn(lambda: instruction_mix.run_mix("load_sum", x, 4),
                       reps=5, warmup=1, bytes_per_call=float(64 * 1024 * 4))
    assert t.mean_s > 0 and len(t.times_s) == 5
    assert len(t.cumulative_mean_s) == 5
    np.testing.assert_allclose(t.cumulative_mean_s[-1], t.mean_s, rtol=1e-9)
    assert t.gbps > 0


def test_time_fn_rejects_degenerate_repetition_counts():
    """reps=0 used to sail through to np.mean([]) — a RuntimeWarning and a
    NaN TimingResult instead of an error (BenchSpec validates its own path;
    this guards direct callers of the harness)."""
    fn = lambda: instruction_mix.run_mix("load_sum",
                                         buffers.working_set(4096), 1)
    with pytest.raises(ValueError, match="reps"):
        timing.time_fn(fn, reps=0)
    with pytest.raises(ValueError, match="reps"):
        timing.time_fn(fn, reps=-1)
    with pytest.raises(ValueError, match="warmup"):
        timing.time_fn(fn, reps=1, warmup=-1)
    # warmup=0 stays valid (first timed rep compiles)
    t = timing.time_fn(fn, reps=1, warmup=0)
    assert t.mean_s > 0


def test_spec_validates_repetition_and_device_knobs():
    """The BenchSpec layer of the same regression: degenerate knobs surface
    at construction, before any timing is spent."""
    from repro.bench import BenchSpec, BenchSpecError
    with pytest.raises(BenchSpecError):
        BenchSpec(reps=0)
    with pytest.raises(BenchSpecError):
        BenchSpec(warmup=-1)
    with pytest.raises(BenchSpecError):
        BenchSpec(devices=0)
    assert BenchSpec(reps=1, warmup=0).devices == 1


def test_mix_kernels_defeat_hoisting():
    """2x passes must take ~2x work: if XLA hoisted the body out of the loop,
    time would be flat in passes.  We check the *result* scales (the accumulator
    sums passes once per iteration)."""
    x = buffers.working_set(32 * 1024, value=2.0)
    a = float(instruction_mix.run_mix("fma_2", x, 2))
    b = float(instruction_mix.run_mix("fma_2", x, 4))
    # fma chain on (v,1/v,-v,-1/v) data: each pass adds ~constant epsilon-sum
    assert abs(b) > abs(a) * 1.5 or abs(b - 2 * a) < 1e-2 * max(abs(a), 1.0)


# ---------------------------------------------------------------------------
# sweep + analysis
# ---------------------------------------------------------------------------

def test_small_sweep_and_analysis():
    res = sweep.run_sweep(sizes=[16 * 2**10, 256 * 2**10, 4 * 2**20],
                          mix_names=["load_sum", "fma_8"], reps=3,
                          target_bytes=3e7)
    assert len(res.points) == 6
    for p in res.points:
        assert p.gbps > 0
    host = detect_host()
    model = analysis.build_machine_model(res, host)
    assert model.level_bw, "no levels attributed"
    for lvl, mixes in model.mix_penalty.items():
        assert max(mixes.values()) == pytest.approx(1.0)


def test_ridge_depth_detects_knee():
    """Synthetic sweep where fma_16 is slower => ridge at 16."""
    pts = []
    for k, bw in [(1, 100.0), (4, 99.0), (16, 50.0), (64, 20.0)]:
        pts.append(sweep.SweepPoint(nbytes=16 * 2**10, mix=f"fma_{k}",
                                    dtype="float32", passes=1, mean_s=1e-3,
                                    std_s=0, gbps=bw, gflops=0))
    pts.append(sweep.SweepPoint(nbytes=16 * 2**10, mix="load_sum",
                                dtype="float32", passes=1, mean_s=1e-3,
                                std_s=0, gbps=100.0, gflops=0))
    res = sweep.SweepResult(points=pts)
    k = analysis.ridge_depth(res, (8 * 2**10, 32 * 2**10))
    assert k == 16


def test_sweep_json_roundtrip(tmp_path):
    res = sweep.run_sweep(sizes=[16 * 2**10], mix_names=["load_sum"], reps=2,
                          target_bytes=1e6)
    p = tmp_path / "sweep.json"
    res.to_json(p)
    back = sweep.SweepResult.from_json(p)
    assert back.points[0].gbps == pytest.approx(res.points[0].gbps)


def test_machine_model_spec():
    assert TPU_V5E.peak_flops == 197e12
    assert TPU_V5E.levels[-1].read_bw == 819e9
    assert TPU_V5E.link_bw == 50e9
    host = detect_host()
    assert host.levels[-1].name == "DRAM"
