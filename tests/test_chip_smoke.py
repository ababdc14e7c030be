"""The on-chip entry and the device-facing edge it guards: ``chip_smoke.py``
refuses a CPU unless the caller types ``--cpu-dry-run``, the compile cache
can be placed from outside, an unknown chip has no peak, and the training
benchmarks report what they ran on."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).parent.parent


def _smoke(*args, env_extra=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"), *args],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_chip_smoke_refuses_cpu_without_the_flag():
    proc = _smoke()
    assert proc.returncode != 0
    assert proc.stdout == ""                      # no result line at all
    assert "'cpu'" in proc.stderr and "needs 'tpu'" in proc.stderr


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    """A copy of the script without the rest of the repo has no program to
    run: it must fail, not report ok."""
    (tmp_path / "chip_smoke.py").write_text(
        (ROOT / "chip_smoke.py").read_text())
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py"), "--cpu-dry-run"],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "petastorm_tpu" in proc.stderr


def test_chip_smoke_cpu_dry_run(tmp_path):
    """The rehearsal the sandbox can do: every leg runs through the real
    entry points at toy size on a 2-device CPU mesh, every line says
    dry_run, and a cache placed from outside is the one that fills."""
    cache = tmp_path / "placed_cache"
    proc = _smoke("--cpu-dry-run",
                  env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    assert all(ln["dry_run"] is True for ln in lines)
    first, final, verdict = lines[0], lines[-2], lines[-1]
    # the last line is the verdict the driver parses: these keys only
    # (plus the rehearsal's own mark)
    assert verdict == {"ok": True, "device": first["device"],
                       "dry_run": True}
    assert first["device"] == {"platform": "cpu", "kind": "cpu", "count": 2}
    assert first["compile_cache_dir"] == str(cache)
    assert final["ok"] is True and final["failures"] == []
    assert final["device"] == first["device"]
    assert list(final["legs"]) == ["image", "tokens_4k_flash",
                                   "tokens_32k_flash",
                                   "tokens_4k_flash_mesh"]
    for leg in final["legs"].values():
        assert leg["ok"] and leg["platform"] == "cpu" and leg["devices"] == 2
        assert len(leg["losses"]) >= 3
        for layout in leg["staged_layouts"]:
            assert all(shards == 2 and on_devices == 2
                       for _shape, shards, _rows, on_devices in layout)
    assert final["legs"]["tokens_4k_flash_mesh"]["mesh_hosts"] == 2
    assert final["native"]["imgcodec"] is True
    # the ResNet compile is slow enough for JAX to persist even on a CPU
    assert final["compile_cache"]["entries_after"] >= 1
    assert os.listdir(cache)


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    import jax

    from petastorm_tpu.jax import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.ensure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    import jax

    from petastorm_tpu.jax import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.ensure_compile_cache()
        assert first == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert compile_cache.ensure_compile_cache() == first   # twice the same
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def _with_peak(monkeypatch, peak):
    from petastorm_tpu.benchmark import imagenet_bench
    monkeypatch.setitem(imagenet_bench._PEAK_BF16_FLOPS, "TPU test", peak)


def test_utilization_metrics_drops_impossible_pipelined_mfu(monkeypatch):
    """A loader-bound pipelined window can yield achieved > chip peak
    (wall - wait underestimates step time when device execution overlaps
    a loader wait). Those bogus pipelined numbers must be dropped — with
    an explanatory note — while the resident metrics stay."""
    from petastorm_tpu.benchmark.imagenet_bench import utilization_metrics

    _with_peak(monkeypatch, 1e12)
    out = {}
    # 1e13 flops in 1 ms -> 1e16 flops/s, 10000x the 1e12 peak;
    # resident: 1e13 / 20s = 5e11 flops/s = a plausible 50% MFU.
    utilization_metrics(out, 1e13, 1e-3, resident_s=20.0, platform="tpu",
                        device_kind="TPU test")
    assert "mfu_pct" not in out
    assert "achieved_tflops_per_chip" not in out
    assert "mfu_pipelined_dropped" in out
    assert out["mfu_pct_resident"] == pytest.approx(50.0)
    assert out["achieved_tflops_per_chip_resident"] == pytest.approx(0.5)


def test_utilization_metrics_drops_impossible_resident_mfu(monkeypatch):
    """The resident window gets the same physical-plausibility bar: a rate
    above chip peak means the sync lied, and no MFU is carried at all."""
    from petastorm_tpu.benchmark.imagenet_bench import utilization_metrics

    _with_peak(monkeypatch, 1e12)
    out = {}
    # pipelined plausible (50%), resident impossible (1e13/1e-3 = 1e16/s)
    utilization_metrics(out, 1e13, 20.0, resident_s=1e-3, platform="tpu",
                        device_kind="TPU test")
    assert out["mfu_pct"] == pytest.approx(50.0)
    assert "mfu_pct_resident" not in out
    assert "achieved_tflops_per_chip_resident" not in out
    assert "mfu_resident_dropped" in out


def test_utilization_metrics_plausible_rate_keeps_pipelined_mfu(monkeypatch):
    from petastorm_tpu.benchmark.imagenet_bench import utilization_metrics

    _with_peak(monkeypatch, 1e15)
    out = {}
    utilization_metrics(out, 1e12, 1e-2, resident_s=None, platform="tpu",
                        device_kind="TPU test")
    # 1e14 flops/s on a 1e15 peak = 10% MFU, physically plausible
    assert out["mfu_pct"] == pytest.approx(10.0)
    assert "mfu_pipelined_dropped" not in out


def test_utilization_metrics_unknown_chip_raises_and_cpu_has_no_mfu():
    from petastorm_tpu.benchmark.imagenet_bench import utilization_metrics

    with pytest.raises(ValueError, match="TPU v9000"):
        utilization_metrics({}, 1e12, 1e-2, None, platform="tpu",
                            device_kind="TPU v9000")
    out = {}
    utilization_metrics(out, 1e12, 1e-2, None, platform="cpu",
                        device_kind="cpu")
    assert "mfu_pct" not in out and out["achieved_tflops_per_chip"] > 0


def test_mosaic_kernels_reads_names_off_the_compiled_text():
    from petastorm_tpu.benchmark.imagenet_bench import mosaic_kernels

    class Compiled:
        def as_text(self):
            return "\n".join([
                '%a = bf16[1] custom-call(%x), custom_call_target='
                '"tpu_custom_call", metadata={op_name="jit(step_fn)/jvp('
                'flash_fwd)/pallas_call" stack_frame_id=9}',
                '%b = bf16[1] custom-call(%x), custom_call_target='
                '"tpu_custom_call", metadata={op_name="jit(step_fn)/'
                'transpose(jvp(flash_bwd_dq))/pallas_call"}',
                '%c = bf16[1] custom-call(%x), custom_call_target='
                '"tpu_custom_call", metadata={op_name="jit(step_fn)/jvp('
                'flash_fwd)/pallas_call"}',
                '%d = f32[1] custom-call(%x), custom_call_target="Sharding"',
            ])

    assert mosaic_kernels(Compiled()) == ["flash_bwd_dq", "flash_fwd"]


def test_llm_bench_flash_on_untileable_window_raises_before_tracing():
    """flash=True asked for the kernel: a window its tiles cannot divide
    is an error before the store is even opened (the URL does not exist),
    never a quiet dense-attention run."""
    from petastorm_tpu.benchmark.llm_bench import run_llm_bench

    with pytest.raises(ValueError, match="cannot tile"):
        run_llm_bench("file:///nonexistent/store", window=100, flash=True)


def test_make_flash_attention_raises_where_flash_attention_falls_back():
    import jax.numpy as jnp

    from petastorm_tpu.ops.flash_attn import (flash_attention,
                                              make_flash_attention)

    q = jnp.zeros((1, 100, 2, 8), jnp.float32)
    assert flash_attention(q, q, q, causal=True).shape == q.shape  # dense
    with pytest.raises(ValueError, match="cannot tile"):
        make_flash_attention(causal=True)(q, q, q)


def test_pallas_interpret_is_chosen_on_cpu_only(monkeypatch):
    import jax

    from petastorm_tpu.ops import flash_attn

    assert flash_attn._resolve_interpret(None) is True       # tests: cpu
    assert flash_attn._resolve_interpret(False) is False
    for backend in ("tpu", "gpu", "some_plugin"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert flash_attn._resolve_interpret(None) is False
        assert flash_attn._resolve_interpret(False) is False
        with pytest.raises(ValueError, match="cpu only"):
            flash_attn._resolve_interpret(True)


# ----------------------------------------------------- the checks themselves
# The chip-only checks never execute in the sandbox (the dry run returns
# before them), so each one is driven here on a fabricated result.

def _load(name, filename):
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, ROOT / filename)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_GOOD_LEG = dict(
    platform="tpu", devices=4, losses=[5.0, 4.5, 4.0],
    staged_layouts=[(((32, 8), 4, (8,), 4), ((32,), 4, (8,), 4))],
    peak_bytes_in_use=[1 << 30] * 4, mosaic_kernels=["a", "b", "c"],
    step_time_ms_resident=10.0, step_time_ms_resident_block_until_ready=10.5)


@pytest.mark.parametrize("change, needle", [
    ({}, None),
    ({"platform": "cpu"}, "expected 4 x tpu"),
    ({"devices": 1}, "expected 4 x tpu"),
    ({"losses": [5.0, float("nan"), 4.0]}, "non-finite loss"),
    ({"losses": [5.0, 4.0, 5.5]}, "loss did not fall"),
    ({"staged_layouts": [(((32, 8), 1, (32,), 1),)]}, "staged array"),
    ({"staged_layouts": [(((32, 8), 4, (8,), 2),)]}, "staged array"),
    ({"peak_bytes_in_use": [1, None, 1, 1]}, "no peak memory"),
    ({"mosaic_kernels": ["a", "b"]}, "fewer than three Mosaic kernels"),
    ({"step_time_ms_resident_block_until_ready": 0.4},
     "sync methods disagree"),
])
def test_chip_smoke_check_catches(change, needle):
    smoke = _load("chip_smoke_under_test", "chip_smoke.py")
    bad = smoke._check({**_GOOD_LEG, **change}, platform="tpu", n_devices=4,
                       global_batch=32, falling=True, flash=True,
                       dry_run=False)
    if needle is None:
        assert bad == []
    else:
        assert len(bad) == 1 and needle in bad[0]


def test_staged_layout_reads_the_shards_themselves():
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.benchmark.imagenet_bench import (recording_layouts,
                                                        staged_layout)

    mesh = Mesh(np.array(jax.devices()), ("data",))
    n = len(jax.devices())
    sharded = jax.device_put(np.zeros((2 * n, 3), np.float32),
                             NamedSharding(mesh, P("data")))
    lone = jax.device_put(np.zeros((2 * n, 3), np.float32), jax.devices()[0])
    assert staged_layout({"x": sharded}) == (((2 * n, 3), n, (2,), n),)
    assert staged_layout(lone) == (((2 * n, 3), 1, (2 * n,), 1),)
    seen = set()
    assert list(recording_layouts(iter([sharded, lone]), seen)) == [sharded,
                                                                    lone]
    assert len(seen) == 2


def test_pipelined_window_reports_every_loss_and_both_closings():
    import jax.numpy as jnp

    from petastorm_tpu.benchmark.imagenet_bench import pipelined_window

    batches = iter(range(1, 100))
    obs = pipelined_window(lambda b: jnp.float32(b), lambda: next(batches),
                           steps=3, resident_steps=2,
                           warm_loss=jnp.float32(0.0))
    assert obs["losses"] == [0.0, 1.0, 2.0, 3.0]
    assert (obs["loss_first"], obs["loss_last"]) == (0.0, 3.0)
    assert obs["resident_s"] > 0 and obs["resident_block_until_ready_s"] > 0
    assert pipelined_window(lambda b: jnp.float32(b), lambda: next(batches),
                            steps=1, resident_steps=0,
                            warm_loss=jnp.float32(0.0))["resident_s"] is None


def test_flash_launch_tiles_hold_at_the_smoke_windows():
    """4k and 32k — the chip legs — tile at the launch defaults, 256x1024."""
    from petastorm_tpu.ops.flash_attn import require_flash_tiles

    assert require_flash_tiles(4096, 4096, causal=True) == (256, 1024)
    assert require_flash_tiles(32768, 32768, causal=True) == (256, 1024)
    with pytest.raises(ValueError, match="cannot tile"):
        require_flash_tiles(4096, 2048, causal=True)   # causal needs sq == sk


def test_commit_batch_surfaces_device_failures(monkeypatch):
    """The staging fallback is for an odd leaf (TypeError/ValueError); a
    runtime failure on the device must surface, not be retried quietly."""
    import jax
    import numpy as np

    from petastorm_tpu.jax import DataLoader

    loader = object.__new__(DataLoader)
    loader._commit_cache = {}
    cols = {"a": np.arange(4, dtype=np.int32)}

    def failing_jit(error):
        def jit(fn):
            raise error
        return jit

    monkeypatch.setattr(jax, "jit", failing_jit(RuntimeError("device lost")))
    with pytest.raises(RuntimeError, match="device lost"):
        loader._commit_batch(cols)
    monkeypatch.setattr(jax, "jit", failing_jit(TypeError("odd leaf")))
    staged = loader._commit_batch(cols)
    assert np.array_equal(np.asarray(staged["a"]), cols["a"])


def test_graft_entry_dry_run_refuses_a_live_accelerator(monkeypatch):
    """__graft_entry__ is a CPU-only dry run: in a process whose backend is
    not the CPU it raises instead of clearing backends under the owner."""
    import types

    import jax

    graft = _load("graft_entry_under_test", "__graft_entry__.py")
    assert len(graft._virtual_cpu_devices(2)) >= 2       # tests run on CPU
    monkeypatch.setattr(
        jax, "devices", lambda: [types.SimpleNamespace(platform="tpu")])
    with pytest.raises(RuntimeError, match="CPU-only dry run"):
        graft._virtual_cpu_devices(2)
    assert "clear_backends" not in (ROOT / "__graft_entry__.py").read_text()
