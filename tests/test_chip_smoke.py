"""chip_smoke.py off the chip, the compile-cache helper, and the children
that are pinned to the CPU through their environment.

The smoke itself passes only on a TPU; what can be held here is that it
FAILS anywhere else without running a phase, that its helpers read the
trainer's output the way the trainer writes it, and that the cache
directory is where the contract says.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from distributed_tensorflow_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


@pytest.mark.parametrize("argv", [[], ["--multichip"]])
def test_smoke_fails_at_the_gate_on_the_cpu_platform(argv):
    """Exit non-zero, the reason on stderr, no phase started, and no
    line that says ``"ok": true``."""
    p = subprocess.run([sys.executable, "chip_smoke.py", *argv], cwd=REPO,
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "not 'tpu'" in p.stderr and "no phase runs" in p.stderr
    assert '"ok": true' not in p.stdout and '"ok": true' not in p.stderr
    assert "== " not in p.stdout  # every phase announces itself so
    assert '"platform": "cpu"' in p.stdout  # the gate did name the device


def test_smoke_takes_no_option_but_multichip():
    p = subprocess.run([sys.executable, "chip_smoke.py", "--cpu"], cwd=REPO,
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=60)
    assert p.returncode == 2 and '"ok"' not in p.stdout


def test_smoke_alone_in_a_directory_fails_before_any_child(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=60)
    assert p.returncode != 0 and "not a checkout" in p.stderr
    assert '"ok"' not in p.stdout


def test_gate_holds_platform_and_count():
    v5e = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    chip_smoke._require_tpu(v5e, 1)
    with pytest.raises(chip_smoke.SmokeError, match="needs 4 chip"):
        chip_smoke._require_tpu(v5e, 4)
    with pytest.raises(chip_smoke.SmokeError, match="'cpu', not 'tpu'"):
        chip_smoke._require_tpu({**v5e, "platform": "cpu"}, 1)


def test_smoke_reads_the_trainers_own_lines():
    """The display and test lines exactly as utils/metrics.py prints
    them (the reference's stdout format)."""
    out = ("job: worker/0 step:  0 mini_batch loss:  4.87 training "
           "accuracy:  0.125\n"
           "job: worker/0 step:  150 mini_batch loss:  nan training "
           "accuracy:  0.0625\n"
           "test accuracy:  0.195 test loss:  2.21\n")
    assert chip_smoke._DISPLAY.findall(out) == [
        ("0", "4.87", "0.125"), ("150", "nan", "0.0625")]
    assert chip_smoke._TEST.search(out).groups() == ("0.195", "2.21")


def test_smoke_prompts_are_fixed_and_in_vocab():
    a, b = chip_smoke._prompts(32768), chip_smoke._prompts(32768)
    assert a == b and len(a) == chip_smoke.SERVE_REQUESTS
    assert len({tuple(p) for p in a}) == len(a)
    assert all(len(p) == chip_smoke.SERVE_PROMPT_LEN
               and all(0 <= t < 32768 for t in p) for p in a)


# ------------------------------------------------------- compile cache


def test_cache_dir_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    assert first == os.path.join(REPO, ".jax_cache")
    # git ignores it; neither the process nor the clock is in the name
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    assert str(os.getpid()) not in first


def test_cache_dir_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


@pytest.mark.parametrize("placed", [True, False])
def test_enable_sets_no_directory_when_the_environment_places_it(
        placed, tmp_path):
    """In a fresh interpreter: with JAX_COMPILATION_CACHE_DIR set JAX
    reads it itself and the helper touches no setting; without it the
    helper points JAX at the checkout's directory."""
    code = ("import jax, json\n"
            "from distributed_tensorflow_tpu.utils import compile_cache\n"
            "jax.config.update = lambda *a, **k: calls.append(a)\n"
            "calls = []\n"
            "path = compile_cache.enable_compile_cache()\n"
            "print(json.dumps({'path': path, 'calls': calls,\n"
            "    'jax': jax.config.jax_compilation_cache_dir}))\n")
    extra = {compile_cache.ENV_VAR: str(tmp_path)} if placed else {}
    env = _cpu_env(**extra)
    if not placed:
        env.pop(compile_cache.ENV_VAR, None)
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    got = json.loads(p.stdout.strip().splitlines()[-1])
    if placed:
        assert got == {"path": str(tmp_path), "calls": [],
                       "jax": str(tmp_path)}
    else:
        want = os.path.join(REPO, ".jax_cache")
        assert got["path"] == want
        assert got["calls"] == [["jax_compilation_cache_dir", want]]


def test_the_cache_key_covers_the_scope_catalog_and_not_the_source():
    """An executable compiled before the program named its blocks must not
    be loaded for the program that names them (the trace would show the
    old paths): JAX's key leaves metadata out, so the catalog goes in
    through ``cache_key.custom_hook``. A scope alone, or a moved line,
    still changes no key: checkouts that share the catalog share entries."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax._src import cache_key, compiler

    from distributed_tensorflow_tpu.utils.telemetry import SCOPES

    def key(scoped):
        def f(x):
            if scoped:
                with jax.named_scope("mlp"):
                    return jnp.tanh(x @ x).sum()
            return jnp.tanh(x @ x).sum()

        module = jax.jit(f).lower(jnp.ones((8, 8))).compiler_ir()
        options = compiler.get_compile_options(num_replicas=1,
                                               num_partitions=1)
        return cache_key.get(module, np.array(jax.devices()[:1]), options,
                             jax.devices()[0].client)

    before = cache_key.custom_hook
    try:
        cache_key.custom_hook = lambda: ""
        bare = key(False)
        assert key(True) == bare  # metadata is not in JAX's key
        compile_cache.enable_compile_cache()
        assert cache_key.custom_hook() == "scopes=" + ",".join(SCOPES)
        assert key(True) == key(False) != bare
    finally:
        cache_key.custom_hook = before


def test_a_later_process_counts_its_persistent_cache_hits(tmp_path):
    """What chip_smoke holds the later trainer to: ``compile_cache_hits``
    of the compile sentry is 0 in the process that stores a program and
    counts the load in the next one (the cache is off for the tests, so
    the children turn it on and store every program, however small)."""
    code = ("import jax, jax.numpy as jnp, json\n"
            "from distributed_tensorflow_tpu.utils import resources\n"
            "sentry = resources.CompileSentry()\n"
            "resources._install_compile_listener()\n"
            "resources.activate(sentry=sentry)\n"
            "jax.jit(lambda x: (x @ x).sum())(jnp.ones((8, 8)))"
            ".block_until_ready()\n"
            "print(json.dumps(sentry.scalars()))\n")
    env = _cpu_env(**{compile_cache.ENV_VAR: str(tmp_path),
                      "JAX_ENABLE_COMPILATION_CACHE": "true",
                      "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                      "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"})
    hits = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr
        got = json.loads(p.stdout.strip().splitlines()[-1])
        assert got["compiles_total"] >= 1
        hits.append(got["compile_cache_hits"])
    assert hits[0] == 0 and hits[1] >= 1, hits


# ------------------------------------- children that must stay off the chip


def test_a_cpu_pinned_child_never_loads_the_tpu_library():
    """bench.py and tools/analyze.py start ``tools.dttcheck`` from a
    parent that may hold the chip; the child is pinned to the CPU through
    JAX_PLATFORMS alone. Under that pin the TPU library is never mapped
    into the process, so it cannot contend for the chip's lock."""
    code = ("import jax, jax.numpy as jnp\n"
            "jnp.ones(3).sum().block_until_ready()\n"
            "print(jax.devices()[0].platform, any('libtpu' in l "
            "for l in open('/proc/self/maps')))\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == ["cpu", "False"]


def test_the_ps_role_never_initialises_a_backend():
    """The reference topology puts ps tasks beside the workers on one
    host; a ps that touched the accelerator would take the chip from the
    worker. Serve an init, a push and a pull, then look."""
    code = (
        "import numpy as np\n"
        "from distributed_tensorflow_tpu.parallel.ps_emulation import (\n"
        "    PSClient, PSServer)\n"
        "server = PSServer(0, '127.0.0.1:0'); server.start_background()\n"
        "c = PSClient([server.address])\n"
        "flat = {'w': np.ones((4, 4), np.float32)}\n"
        "c.init_params(flat, {'w': 0}, optimizer='adam',\n"
        "              learning_rate=0.1, num_workers=1)\n"
        "c.push_grads({'w': np.ones((4, 4), np.float32)}, {'w': 0})\n"
        "pulled, _ = c.pull_all()\n"
        "c.close(); server.close()\n"
        "import jax._src.xla_bridge as xb\n"
        "print(float(pulled['w'][0, 0]) < 1.0, "
        "xb.backends_are_initialized())\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=_cpu_env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == ["True", "False"]
