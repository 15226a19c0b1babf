"""Fully device-resident loop engine (DESIGN.md §4i): golden-hash parity
with the pipelined superstep engine at depth 1, loop-counter consistency,
the warm-pool cache-hit counter, snapshot + bit-identical resume at chunk
granularity, the OOM rung-ladder fallback, the fp16 score-cache knob, the
interpret-mode override, and parameter validation."""
import dataclasses
import hashlib

import numpy as np
import pytest

from repro.core import metrics, resilience
from repro.engines.device import DeviceParams, hype_device_partition
from repro.engines.superstep import (SuperstepParams,
                                     hype_superstep_partition)
from repro.data.synthetic import powerlaw_hypergraph, reddit_like


def _digest(a: np.ndarray) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(a, dtype=np.int32).tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def hg():
    return powerlaw_hypergraph(600, 400, seed=11, max_edge=30,
                               max_degree=20)


@pytest.fixture(scope="module")
def dev_16_8(hg):
    """One shared (k=16, t=8) device run: parity + counter tests below
    all read it, so the while_loop program compiles once per module.
    The empty plan pins the DEVICE path: the counter/host-fraction
    assertions measure the loop itself, so an env-injected fault
    (chaos/low-memory CI) must not push this run onto the fallback."""
    return hype_device_partition(
        hg, 16, DeviceParams(seed=0, t=8,
                             fault_plan=resilience.FaultPlan()),
        return_stats=True)


# --------------------------------------------------- golden-hash parity

# The exact digests test_pipeline.py pins for hype_superstep at
# pipeline_depth=1: the device loop runs the same lock-step cadence as
# one on-device program and must land on them bit for bit.
_GOLD_PL600 = {(5, 8): "9e8abe668aa53a74",
               (16, 8): "bbcd2f732e03af91",
               (16, 16): "e67c679d4029b7d0"}
_GOLD_REDDIT = "13f232f653c9c752"


def test_device_bit_identical_16_8(dev_16_8):
    a, _ = dev_16_8
    assert _digest(a) == _GOLD_PL600[(16, 8)]


@pytest.mark.parametrize("k,t", [(5, 8), (16, 16)])
def test_device_bit_identical_powerlaw(hg, k, t):
    a = hype_device_partition(hg, k, DeviceParams(seed=0, t=t))
    assert _digest(a) == _GOLD_PL600[(k, t)]


def test_device_bit_identical_reddit_quick():
    a = hype_device_partition(reddit_like(scale=0.005, seed=0), 32,
                              DeviceParams(seed=0, t=16))
    assert _digest(a) == _GOLD_REDDIT


# ------------------------------------------------- counter consistency

def test_device_loop_counters(dev_16_8):
    """The loop counters must tell a consistent story: at least one
    chunk ran, every superstep is a device round (plus any pack-only
    rounds), the refill triggers came from the kernel, and both the
    one-time image and the resident carry are accounted."""
    _, st = dev_16_8
    assert st.supersteps > 0
    assert st.loop_chunks >= 1
    assert st.loop_rounds >= st.supersteps
    assert st.loop_pack_only >= 0
    assert st.loop_rounds >= st.loop_pack_only
    assert st.refill_signals > 0
    assert st.loop_store_peak > 0
    assert 0 < st.loop_state_bytes < st.device_image_bytes
    assert st.kernel_calls == st.supersteps


def test_device_host_fraction(dev_16_8):
    """The tentpole claim: the host does (almost) nothing per chunk —
    its share of the loop must stay under 10% of total loop time."""
    _, st = dev_16_8
    assert st.device_s > 0.0
    assert st.host_s <= 0.1 * (st.host_s + st.device_s)


def test_device_fallback_counter_is_zero(dev_16_8):
    """A supported graph must run on the device path, not fall back."""
    _, st = dev_16_8
    assert st.fallbacks == 0
    assert st.plan_rung == 0


# ------------------------------------------------ warm-pool cache hits

def test_warm_pool_cache_hits_host(hg):
    """Satellite regression: pool slots re-served from the score cache
    must count as hits (the counter was dead before §4i). A small t
    with a deep pool holds candidates across supersteps, so later
    supersteps serve them from cache."""
    _, st = hype_superstep_partition(
        hg, 16, SuperstepParams(seed=0, t=4, pool_cap=64,
                                pipeline_depth=1,
                                fault_plan=resilience.FaultPlan()),
        return_stats=True)
    assert st.cache_hits > 0


def test_warm_pool_cache_hits_device(hg):
    """The device loop counts the same event on device (S_CACHE_HITS)
    and must agree with the host engine bit for bit — same schedule,
    same held pool, same hits."""
    p_host = SuperstepParams(seed=0, t=4, pool_cap=64, pipeline_depth=1,
                             fault_plan=resilience.FaultPlan())
    a_host, st_host = hype_superstep_partition(hg, 16, p_host,
                                               return_stats=True)
    a_dev, st_dev = hype_device_partition(
        hg, 16, DeviceParams(seed=0, t=4, pool_cap=64,
                             fault_plan=resilience.FaultPlan()),
        return_stats=True)
    np.testing.assert_array_equal(a_dev, a_host)
    assert st_dev.cache_hits > 0
    assert st_dev.cache_hits == st_host.cache_hits


# ------------------------------------- snapshot + bit-identical resume

def test_device_snapshot_resume_bit_identical(hg, tmp_path):
    """Kill a snapshotting device run with an injected fatal fault,
    resume from the chunk-boundary snapshot: the final assignment must
    equal the uninterrupted run's bit for bit."""
    d = str(tmp_path / "killed")
    clean = hype_device_partition(
        hg, 16, DeviceParams(seed=0, t=8))
    with pytest.raises(resilience.UnrecoverableFault):
        hype_device_partition(hg, 16, DeviceParams(
            seed=0, t=8, snapshot_every=4, snapshot_dir=d,
            fault_plan="dispatch@5:fatal"))
    a, st = hype_device_partition(hg, 16, DeviceParams(
        seed=0, t=8, snapshot_every=4, snapshot_dir=d, resume=d),
        return_stats=True)
    np.testing.assert_array_equal(a, clean)
    assert _digest(a) == _GOLD_PL600[(16, 8)]
    assert st.resumed_at >= 4


# ------------------------------------------------ OOM rung-ladder path

def test_device_oom_falls_down_rung_ladder(hg):
    """An injected device OOM mid-loop must fall down the §4g host rung
    ladder (the device program has no reduced-memory variant), finish
    complete and balanced, and report the retry + rung + fallback."""
    a, st = hype_device_partition(
        hg, 16, DeviceParams(seed=0, t=8, fault_plan="oom@2"),
        return_stats=True)
    assert (a >= 0).all() and (a < 16).all()
    sizes = metrics.partition_sizes(a, 16)
    assert sizes.max() - sizes.min() <= 1
    assert st.mem_retries >= 1
    assert st.plan_rung >= 1
    assert st.fallbacks >= 1


# ------------------------------------------------- fp16 score cache

def test_device_fp16_cache(hg, dev_16_8):
    """cache_dtype="float16" halves the resident cache bytes. Scores on
    this graph are small exact integers (< 2048 external neighbors), so
    fp16 storage rounds nothing and the result stays bit-identical; the
    quality band is asserted too so the test degrades gracefully if the
    graph ever grows past the exact-integer range."""
    a32, st32 = dev_16_8
    a16, st16 = hype_device_partition(
        hg, 16, DeviceParams(seed=0, t=8, cache_dtype="float16",
                             fault_plan=resilience.FaultPlan()),
        return_stats=True)
    assert st16.loop_state_bytes < st32.loop_state_bytes
    assert st16.device_image_bytes < st32.device_image_bytes
    np.testing.assert_array_equal(a16, a32)
    km32 = metrics.k_minus_1(hg, a32)
    km16 = metrics.k_minus_1(hg, a16)
    assert km16 <= 1.02 * km32 + 2


# -------------------------------------------- interpret-mode override

def test_device_interpret_mode(monkeypatch, hg, dev_16_8):
    """Forcing interpret mode must still complete and stay on the same
    schedule (on CPU it is the default, so this also guards the env
    plumbing through the device-loop program)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    a = hype_device_partition(hg, 16, DeviceParams(seed=0, t=8))
    np.testing.assert_array_equal(a, dev_16_8[0])


# ------------------------------------------- compile-cache placement

def test_compile_cache_env_knob(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the persistent compile cache
    when set; otherwise it sits at the fixed <checkout>/.jax_cache, and
    no other directory is ever configured."""
    import pathlib

    import jax
    from repro.kernels import _compat
    before = jax.config.jax_compilation_cache_dir
    cc = str(tmp_path / "cc")
    default = str(pathlib.Path(__file__).resolve().parents[1]
                  / ".jax_cache")
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cc)
        assert _compat.configure_compile_cache() == cc
        assert jax.config.jax_compilation_cache_dir == cc
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        for _ in range(2):      # the same path on every call
            assert _compat.configure_compile_cache() == default
            assert jax.config.jax_compilation_cache_dir == default
    finally:     # leave the process-global config as we found it
        jax.config.update("jax_compilation_cache_dir", before)


# -------------------------------------------------- parameter contract

def test_device_param_validation(hg):
    with pytest.raises(ValueError, match="chunk_supersteps"):
        hype_device_partition(hg, 4, DeviceParams(chunk_supersteps=0))
    with pytest.raises(ValueError, match="cache_dtype"):
        hype_device_partition(hg, 4, DeviceParams(cache_dtype="bf16"))
    with pytest.raises(ValueError, match="snapshot_dir"):
        hype_device_partition(hg, 4, DeviceParams(snapshot_every=2))


def test_device_k1_shortcut(hg):
    a = hype_device_partition(hg, 1, DeviceParams(seed=0))
    assert (a == 0).all() and a.dtype == np.int32


def test_device_unsupported_falls_back(hg):
    """A graph/config the int32 encoding gates reject must transparently
    fall back to hype_superstep and still satisfy the contract."""
    from repro.core import device_loop
    # bud * 2^CLS_CLAMP reaches 2^31: the stage-A cumsum could overflow
    assert not device_loop.supported(n=10, m=100, kG=4, bud=1 << 13)
    # rows=2048 -> bud=8192 trips the same gate through the public API
    a, st = hype_device_partition(hg, 3, DeviceParams(seed=0, t=8,
                                                      rows=2048),
                                  return_stats=True)
    assert (a >= 0).all() and (a < 3).all()
    assert st.fallbacks == 1    # the hand-off to hype_superstep shows
