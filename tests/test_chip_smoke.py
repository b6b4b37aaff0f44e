"""chip_smoke.py's contract, rehearsed on the CPU: it refuses a non-TPU
device unless told it is a rehearsal, it never answers from the CPU after
a device fault, and it keeps its compile cache where the environment (or
the checkout) says."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(argv, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env)
    return subprocess.run(
        [sys.executable] + argv, env=e, cwd=ROOT, capture_output=True,
        text=True, timeout=600,
    )


def _last_json(out):
    try:
        return json.loads(out.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return None


def test_chip_smoke_refuses_the_cpu_without_running_a_query():
    out = _run([SMOKE])
    assert out.returncode != 0
    assert "no TPU" in out.stdout
    assert "phase" not in out.stdout and "Q6" not in out.stdout
    assert _last_json(out) is None


def test_chip_smoke_sf_needs_the_rehearsal_flag():
    out = _run([SMOKE, "--sf", "0.01"])
    assert out.returncode != 0
    assert _last_json(out) is None


def test_chip_smoke_rehearsal_passes_and_caches_where_the_env_says(tmp_path):
    cache = tmp_path / "xla"
    out = _run([SMOKE, "--sf", "0.01", "--rehearse-cpu"],
               JAX_COMPILATION_CACHE_DIR=str(cache))
    assert out.returncode == 0, out.stdout[-3000:]
    last = _last_json(out)
    assert last and last["ok"] is True
    assert last["device"]["platform"] == "cpu"  # named for what it is
    assert set(last["device"]) == {"platform", "kind", "count"}
    for q in ("Q6", "Q1", "Q3"):
        assert "ok: %s: answer equals the numpy reference" % q in out.stdout
    assert "compile cache: %s (0 entries at start)" % cache in out.stdout
    assert os.listdir(cache), "no compiled program reached the cache dir"


def test_chip_smoke_fails_rather_than_answering_from_the_cpu():
    driver = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke\n"
        "import trino_tpu.session as S\n"
        "orig = S.tpch_session\n"
        "def faulty(sf, **kw):\n"
        "    kw['fault_injection'] = '{\"device_loss\": {\"nth\": 2}}'\n"
        "    return orig(sf, **kw)\n"
        "S.tpch_session = faulty\n"
        "args = chip_smoke.parse_args(['--sf', '0.01', '--rehearse-cpu'])\n"
        "sys.exit(chip_smoke.Smoke(args).main())\n"
    ) % ROOT
    out = _run(["-c", driver])
    assert out.returncode != 0, out.stdout[-3000:]
    assert _last_json(out) is None
    assert "device_loss" in out.stdout


@pytest.mark.parametrize("named", [True, False])
def test_place_jax_cache_yields_to_the_environment(named, tmp_path):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import jax\n"
        "from trino_tpu.cache.compile_cache import place_jax_cache\n"
        "print(place_jax_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    ) % ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if named:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    want = str(tmp_path) if named else os.path.join(ROOT, ".jax_cache")
    assert out.stdout.split() == [want, want]
