"""chip_smoke.py rehearsed on the CPU, and the no-fallback rules around it.

The smoke's phases are plain functions of a model configuration; the
device check lives in ``main()``. Here each phase runs to its
assertions at gpt_tiny size — the four-chip ones on four of conftest's
virtual devices — so that a chip call finds wrong paths, arguments and
control flow already gone. What a chip run alone can show (that the
kernels compile and run there, that the 1.3B plans fit, the times) is
``python chip_smoke.py`` on the chip; tests/test_chip_compile.py
compiles the kernels for it.

The second half holds the entry points and selectors to the rule this
smoke exists for: with no chip they fail; none answers from a CPU under
a device's name.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import gpt_tiny

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

LIMIT = 24 << 20          # a 24 MiB "device" for the pool plan
BUCKETS = (32, 64)


@pytest.fixture
def aot_cache(tmp_path):
    """The smoke runs with the repo's AOT cache on (``main`` sets it),
    in a process of its own: ``exec_table`` reads every executable the
    registry holds, so what an earlier test file of this worker left
    there (no memory analysis) goes first."""
    from paddle_tpu.compile_cache import reset_default_cache
    from paddle_tpu.observability import xstats
    xstats.reset_for_tests()
    paddle.set_flags({"FLAGS_compile_cache_dir": str(tmp_path / "aot")})
    reset_default_cache()
    yield str(tmp_path / "aot")
    paddle.set_flags({"FLAGS_compile_cache_dir": ""})
    reset_default_cache()


def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *args], env=env,
                          cwd=REPO_ROOT, capture_output=True, text=True,
                          timeout=300)


# -------------------------------------------------- the phases, rehearsed
class TestPhases:
    def test_train_then_release(self, aot_cache):
        out = chip_smoke.phase_train(
            gpt_tiny(stacked=True, recompute="full"), batch=2, seq=64,
            steps=4, expect_kernel=False, amp_level=None,
            moment_dtype="float32")
        assert out["losses"][-1] < out["losses"][0]
        assert out["kernel"] is False      # interpret mode has no call
        # the trainer, its moments and its executables are gone
        assert chip_smoke.release("train", 1 << 30) < 4096

    def test_train_that_expects_the_kernel_fails_without_it(
            self, aot_cache):
        with pytest.raises(RuntimeError, match="no tpu_custom_call"):
            chip_smoke.phase_train(
                gpt_tiny(stacked=True), batch=2, seq=64, steps=2,
                expect_kernel=True, amp_level=None,
                moment_dtype="float32")

    def test_release_refuses_a_device_left_full(self):
        held = jax.numpy.ones((1 << 20,), jax.numpy.float32)
        with pytest.raises(RuntimeError, match="left .* bytes"):
            chip_smoke.release("a phase", limit_bytes=64 << 20)
        del held

    def test_serve_and_warm_restart(self, aot_cache):
        model = chip_smoke.make_serve_model(gpt_tiny())
        out = chip_smoke.phase_serve(
            model, limit_bytes=LIMIT, seq_buckets=BUCKETS, name="smoke",
            restart=True, parity_tol=1e-4)
        assert {k: len(v) for k, v in out["streams"].items()} == \
            {"short": 16, "long": 12, "pair_a": 12, "pair_b": 12}
        # the pool filled what the weights left of the 24 MiB
        assert LIMIT // 4 < out["pool_bytes"] < LIMIT
        sites = {r["site"] for r in out["rows"]}
        assert sites == set(chip_smoke.SERVE_SITES)

    def test_serve_pallas_int8(self, aot_cache):
        model = chip_smoke.make_serve_model(gpt_tiny())
        out = chip_smoke.phase_serve(
            model, limit_bytes=LIMIT, seq_buckets=BUCKETS,
            name="smoke-pallas-int8", use_pallas=True, kv_dtype="int8",
            parity_tol=0.25)
        assert len(out["streams"]["short"]) == 16
        # the kernel was asked for by name, so off the chip too the
        # decode program was traced through it (interpret mode) ...
        from paddle_tpu.framework import flags
        # ... and no flag chose it: there is none, and the pool's dtype
        # flag is back at its default afterwards
        assert not [name for name in flags._REGISTRY if "pallas" in name]
        assert flags.flag_value("FLAGS_decode_kv_dtype") == ""

    def test_serve_parity_catches_a_wrong_token(self, aot_cache):
        model = chip_smoke.make_serve_model(gpt_tiny())
        prompt = chip_smoke.make_traffic(model.config, 16,
                                         BUCKETS)["short"][0]
        ref, gap, spread = chip_smoke.reference_tokens(
            model, prompt, [3, 5, 7])
        assert gap.shape == (3,) and gap.max() > 1e-3 * spread
        ref2, gap2, _ = chip_smoke.reference_tokens(
            model, prompt, list(ref[:1]))
        assert gap2.max() == 0.0 and ref2[0] == ref[0]

    def test_logit_parity_tells_one_context_from_another(
            self, aot_cache, monkeypatch):
        """The rows the decoder computes from its pages are held to the
        forward of the SAME prompt: against the forward of a permuted
        prompt they are far outside the tolerance, so the check sees
        what attention read — equal greedy tokens of a random model
        would not."""
        from paddle_tpu.serving.generation import GenerationServer
        model = chip_smoke.make_serve_model(gpt_tiny())
        prompt = chip_smoke.make_traffic(model.config, 16,
                                         BUCKETS)["short"][0]
        srv = GenerationServer(model, num_pages=33, seq_buckets=BUCKETS,
                               name="parity", start=False)
        new = [int(prompt[-1])] * 3
        d_pre, d_dec, spread = chip_smoke.logit_parity(
            srv, model, prompt, new, BUCKETS[0])
        assert max(d_pre, d_dec) < 1e-4 * spread
        real = chip_smoke.reference_logits
        monkeypatch.setattr(
            chip_smoke, "reference_logits",
            lambda m, p, n: real(m, p[::-1].copy(), n))
        d_pre, d_dec, spread = chip_smoke.logit_parity(
            srv, model, prompt, new, BUCKETS[0])
        assert min(d_pre, d_dec) > 0.25 * spread
        srv.kv.assert_no_leaks()
        srv.shutdown()

    def test_train_over_a_mesh(self, aot_cache):
        out = chip_smoke.phase_train_mesh(
            gpt_tiny(), axes={"dp": 2, "mp": 2}, batch=4, seq=64,
            steps=2, rtol=2e-4)
        np.testing.assert_allclose(out["losses"], out["single"],
                                   rtol=2e-4)
        chip_smoke.release("train x4", 1 << 30)

    def test_serve_over_a_mesh(self, aot_cache):
        """Also the regression for the AOT tier under a live mesh: the
        executables are lowered for the operands' committed shardings
        (they used to be lowered replicated and refuse the first call
        whenever FLAGS_compile_cache_dir was set)."""
        streams = chip_smoke.phase_serve_mesh(
            gpt_tiny(), mp=4, seq_buckets=BUCKETS, num_pages=65)
        assert streams["1 chip"] == streams["mp=4"]

    def test_spread_check_refuses_a_device_holding_the_whole(self):
        devs = jax.devices()[:4]
        with pytest.raises(RuntimeError, match="its share"):
            chip_smoke.assert_spread(
                {devs[0]: 4 << 30, devs[1]: 0, devs[2]: 0, devs[3]: 0},
                total=4 << 30, ways=4)
        chip_smoke.assert_spread({d: 1 << 30 for d in devs},
                                 total=4 << 30, ways=4)

    def test_compile_counter_counts_backend_compiles(self):
        counter = chip_smoke.CompileCounter()
        snap = counter.snapshot()
        jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0))
        assert counter.compile_s > snap[2]
        assert "backend compile or cache load" in counter.since(snap)


# ------------------------------------------- no chip: fail, never fall back
class TestNoChip:
    def test_chip_smoke_fails_without_a_tpu(self):
        res = _run("chip_smoke.py")
        assert res.returncode != 0
        assert '"ok"' not in res.stdout
        assert "needs a TPU" in res.stderr

    def test_chip_smoke_four_chips_fails_without_a_tpu(self):
        res = _run("chip_smoke.py", "--chips", "4")
        assert res.returncode != 0 and '"ok"' not in res.stdout

    def test_bench_fails_without_a_tpu_and_prints_no_record(self):
        res = _run("bench.py")
        assert res.returncode != 0
        assert res.stdout.strip() == ""        # no skip record, no JSON
        assert "measures on a TPU" in res.stderr

    def test_bench_smoke_is_the_cpu_rehearsal(self):
        res = _run("bench.py", "--smoke", "--steps", "2", "--windows", "1")
        assert res.returncode == 0, res.stderr[-2000:]
        rec = json.loads(res.stdout.strip().splitlines()[-1])
        assert rec["platform"] == "cpu" and rec["device_count"] >= 1
        assert rec["device_kind"] and "skipped" not in rec

    def test_tpu_place_raises_instead_of_a_cpu_device(self):
        from paddle_tpu.framework.place import CPUPlace, TPUPlace
        with pytest.raises(RuntimeError, match="needs a tpu device"):
            TPUPlace(0).jax_device()
        assert CPUPlace().jax_device().platform == "cpu"

    def test_unknown_device_kind_has_no_peaks(self):
        from paddle_tpu.observability import xstats
        assert xstats.chip_peaks("TPU v5 lite")["flops"] == 197e12
        assert "source" in xstats.chip_peaks("TPU v5 lite")
        with pytest.raises(ValueError, match="no published peaks"):
            xstats.chip_peaks("cpu")

    def test_fused_kernel_asked_for_never_hands_over_to_the_gather(self):
        """``use_pallas`` with a call the kernel cannot serve raises;
        the gather path does not answer under the kernel's name."""
        import jax.numpy as jnp

        from paddle_tpu.ops.paged_attention import (
            kv_pool_shape, paged_attention_update)
        q = jnp.zeros((1, 1, 2, 8))
        pool = jnp.zeros(kv_pool_shape(3, 1, 2, 8))   # one-slot pages
        args = (q, q, q, pool, pool, jnp.zeros((1, 2), jnp.int32),
                jnp.ones((1,), jnp.int32), jnp.ones((1, 1), bool),
                jnp.zeros((1, 1), jnp.int32))
        with pytest.raises(ValueError, match="cannot serve"):
            paged_attention_update(*args, page_size=1, kind="decode",
                                   use_pallas=True)
        out, _, _ = paged_attention_update(*args, page_size=1,
                                           kind="decode",
                                           use_pallas=False)
        assert out.shape == q.shape


class TestAutotunePick:
    """``autotune.pick`` skips a candidate only for the resource error
    it is meant to skip, and raises when none is left."""

    @pytest.fixture
    def tuning(self, monkeypatch, tmp_path):
        from paddle_tpu.framework import place
        from paddle_tpu.ops import autotune
        monkeypatch.setattr(place, "on_tpu", lambda: True)
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "autotune.json"))
        monkeypatch.setattr(autotune, "_loaded", False)
        monkeypatch.setattr(autotune, "_cache", {})
        return autotune

    @staticmethod
    def _make(failing: dict):
        def make_fn(cand):
            def fn():
                if cand in failing:
                    raise failing[cand]
                return jax.numpy.zeros(())
            return fn
        return make_fn

    def test_vmem_exhaustion_is_skipped_and_the_winner_persisted(
            self, tuning, tmp_path):
        oom = jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem")
        got = tuning.pick("k", (1,), [(1,), (2,)],
                          self._make({(1,): oom}), ())
        assert got == (2,)
        with open(tmp_path / "autotune.json") as f:
            assert json.load(f) == {"k/1": [2]}

    def test_any_other_failure_is_the_kernels_and_is_raised(self, tuning):
        with pytest.raises(ValueError, match="divisible by 8"):
            tuning.pick("k", (2,), [(1,), (2,)],
                        self._make({(1,): ValueError("divisible by 8")}),
                        ())
        bad = jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed")
        with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
            tuning.pick("k", (3,), [(1,), (2,)],
                        self._make({(1,): bad}), ())

    def test_no_candidate_left_raises_and_persists_nothing(
            self, tuning, tmp_path):
        oom = jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: vmem")
        with pytest.raises(RuntimeError, match="none of 2 candidates"):
            tuning.pick("k", (4,), [(1,), (2,)],
                        self._make({(1,): oom, (2,): oom}), ())
        assert not (tmp_path / "autotune.json").exists()

    def test_table_lives_in_the_checkout_not_in_home(self, monkeypatch):
        from paddle_tpu.compile_cache import cache_root
        from paddle_tpu.ops import autotune
        monkeypatch.delenv("PADDLE_TPU_AUTOTUNE_CACHE", raising=False)
        assert autotune._cache_path() == os.path.join(
            cache_root(), "autotune.json")
        assert not autotune._cache_path().startswith(
            os.path.expanduser("~") + os.sep + ".")


# ----------------------------------------------- caches placed from outside
class TestCachePlacement:
    def test_fixed_path_inside_the_checkout(self, monkeypatch):
        from paddle_tpu import compile_cache as cc
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        was = jax.config.jax_compilation_cache_dir
        floor = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            path = cc.place_jax_cache()
            assert path == os.path.join(REPO_ROOT, ".cache", "jax")
            assert jax.config.jax_compilation_cache_dir == path
            assert cc.aot_cache_dir() == os.path.join(
                REPO_ROOT, ".cache", "paddle_aot")
            # the same answer every time: a path that moves never hits
            assert cc.place_jax_cache() == path
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", floor)

    def test_obeys_the_environment_and_sets_no_directory(
            self, monkeypatch, tmp_path):
        from paddle_tpu import compile_cache as cc
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        was = jax.config.jax_compilation_cache_dir
        floor = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            assert cc.place_jax_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was
            assert cc.aot_cache_dir() == str(tmp_path / "paddle_aot")
        finally:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", floor)

    def test_cache_root_is_ignored_by_git(self):
        with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
            ignored = f.read().split()
        assert ".cache/" in ignored and "chiprun_out/" in ignored


# -------------------------------------------------- one process per chip
class TestOneProcessPerChip:
    @pytest.fixture
    def spawned(self, monkeypatch):
        """The environment of every worker a factory starts (nothing
        is started)."""
        from paddle_tpu.serving.fleet import supervisor
        envs = []

        class FakePopen:
            pid = 0

            def __init__(self, cmd, env=None, **kw):
                envs.append(env)

        monkeypatch.setattr(supervisor.subprocess, "Popen", FakePopen)
        return envs

    def test_each_worker_gets_its_own_chip(self, spawned):
        from paddle_tpu.serving.fleet import supervisor
        fac = supervisor.ProcessReplicaFactory(
            extra_args=["--stub"],
            env=lambda rid: {"FLAGS_compile_cache_dir": "/c",
                             **supervisor.one_chip_env(rid % 4)})
        for rid in range(5):
            fac(rid)
        chips = [e["TPU_VISIBLE_CHIPS"] for e in spawned]
        assert chips == ["0", "1", "2", "3", "0"]
        assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1"
                   and e["FLAGS_compile_cache_dir"] == "/c"
                   and "PATH" in e for e in spawned)

    def test_a_dict_env_still_overlays_every_worker_alike(self, spawned):
        from paddle_tpu.serving.fleet import supervisor
        fac = supervisor.ProcessReplicaFactory(env={"JAX_PLATFORMS": "cpu"})
        fac(0), fac(1)
        assert [e["JAX_PLATFORMS"] for e in spawned] == ["cpu", "cpu"]

    def test_bench_fleet_parent_never_asks_for_the_default_backend(self):
        """The parent of device-owning workers stays off the chips: the
        only backend query left in tools/bench_fleet.py is the --mesh
        slice's, which is one process."""
        with open(os.path.join(REPO_ROOT, "tools", "bench_fleet.py")) as f:
            src = f.read()
        body = src[src.index("def _run(args):"):]
        assert "import jax" not in body and "default_backend" not in body
        assert src.count("default_backend()") == 1


def test_native_library_says_how_it_came_to_be():
    from paddle_tpu import native
    handle = native.lib()
    status = native.status()
    if handle is None:
        assert status and "not loaded yet" not in status
    else:
        assert status in ("built from csrc/",
                          "loaded (built earlier from the same csrc/)")


def test_native_build_reports_a_missing_compiler(monkeypatch, tmp_path):
    from paddle_tpu import native

    def no_gxx(cmd, **kw):
        raise FileNotFoundError(cmd[0])

    monkeypatch.setattr(native.subprocess, "run", no_gxx)
    assert native._build(str(tmp_path), str(tmp_path / "x.so")) == \
        "g++ is not installed"
