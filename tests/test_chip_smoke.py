"""``chip_smoke.py`` on the CPU: its phase functions at the qwen3-4b smoke
config with Pallas in interpret mode (control flow and every check), and
the script itself refusing to report a result without a TPU."""
import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import configs
from repro.models import zoo
from repro.serve.engine import ContinuousServeConfig

ROOT = Path(__file__).resolve().parents[1]
SCFG = ContinuousServeConfig(slots=3, max_len=64, page_size=8, prefill_chunk=8)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve their module by name
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("qwen3-4b")
    return cfg, zoo.init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["ref", "pallas"])
def test_serve_phase_and_logit_check(smoke, model, use_pallas):
    cfg, params = model
    sets = [smoke.make_requests(cfg, 3, (10, 20), 4, seed) for seed in (0, 1)]
    assert any(sp.temperature > 0 for _, sp in sets[0]) and any(sp.temperature <= 0 for _, sp in sets[0])
    engine, runs = smoke.serve_phase(
        "test", cfg, params, dataclasses.replace(SCFG, use_pallas=use_pallas), sets, smoke.CompileMeter()
    )
    assert [r["tokens"] for r in runs] == [12, 12]
    assert runs[0]["compile_s"] > 0.0
    prompt = sets[0][0][0]
    tokens = prompt + runs[0]["outputs"][0][:3]
    fwd = smoke.forward_logits(cfg, params, tokens, len(prompt))
    assert fwd.shape == (4, cfg.vocab)
    assert smoke.check_logits("test", engine, tokens, len(prompt), fwd) <= smoke.LOGIT_RTOL


def test_kernel_check_at_smoke_widths(smoke, model):
    cfg, _ = model
    assert smoke.check_kernel(cfg, max_len=64, page_size=8, batch=2, seed=0) <= smoke.KERNEL_RTOL


def test_compiled_kernel_check_refuses_interpret_mode(smoke, model):
    """Off the chip the Pallas policy interprets, and the datapath check
    must say so rather than pass."""
    cfg, params = model
    engine, _ = smoke.serve_phase("test", cfg, params, dataclasses.replace(SCFG, use_pallas=True), [])
    assert engine.policy.interpret is True
    with pytest.raises(AssertionError, match="interpreted"):
        smoke.check_compiled_kernels(engine)
    assert "tpu_custom_call" not in smoke.decode_step_hlo(engine)


def test_serve_check_catches_a_short_request(smoke, model):
    cfg, params = model
    (prompt, sp), = smoke.make_requests(cfg, 1, (10, 10), 4, 0)
    engine, (run,) = smoke.serve_phase("test", cfg, params, SCFG, [[(prompt, sp)]])
    stop_early = sp.with_stop(run["outputs"][0][0])  # stop at the first token
    with pytest.raises(AssertionError, match="generated 1 of 4"):
        smoke.serve(engine, [(prompt, stop_early)])


def test_one_chip_run_reaches_the_compiled_kernel_check(smoke, model):
    """Phases 2-4 end to end at a tiny shape: everything passes on the CPU
    up to the check that only a chip can pass."""
    cfg, params = model
    shape = smoke.Shape(slots=2, max_len=64, page_size=8, requests=2, prompt_len=(10, 16), max_new=3,
                        check_decode=2)
    with pytest.raises(AssertionError, match="interpreted"):
        smoke.run_one_chip(cfg, params, shape, 0, smoke.CompileMeter())


def test_four_chip_run_on_virtual_devices():
    """The --chips 4 path on four virtual CPU devices, with a smoke config
    that has 4 KV heads (one per shard)."""
    code = (
        "import dataclasses, jax, chip_smoke as cs\n"
        "from repro import configs\n"
        "from repro.models import zoo\n"
        "cfg = dataclasses.replace(configs.get_smoke('qwen3-4b'), heads=8, kv_heads=4)\n"
        "params = zoo.init_params(jax.random.PRNGKey(0), cfg)\n"
        "shape = cs.Shape(slots=2, max_len=64, page_size=8, requests=2, prompt_len=(10, 16), max_new=3,"
        " check_decode=2)\n"
        "cs.run_four_chips(cfg, params, shape, 0)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "tokens agree in 2/2 requests" in out.stdout
    assert "per device" in out.stdout


def test_greedy_agreement_counts_greedy_rows_only(smoke, model):
    cfg, _ = model
    reqs = smoke.make_requests(cfg, 4, (4, 4), 2, 0)
    a = [[1, 2], [3, 4], [5, 6], [7, 8]]
    b = [[1, 2], [0, 0], [5, 0], [0, 0]]
    assert smoke.greedy_agreement(reqs, a, b) == (1, 2)


def _run_script(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env, capture_output=True, text=True, timeout=300
    )


def test_script_fails_without_a_tpu():
    out = _run_script(ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_script_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run_script(tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_location(tmp_path, env_dir):
    """The cache stays where JAX_COMPILATION_CACHE_DIR says, else it is the
    fixed directory in the checkout (run in a child so this process never
    switches the cache on)."""
    code = (
        "import jax\n"
        "from repro.launch.runtime import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    used, configured = out.stdout.split()
    want = str(tmp_path / env_dir) if env_dir else str(ROOT / ".jax_cache")
    assert used == want and configured == want
