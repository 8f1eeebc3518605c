"""Device selection and start-up: ``mx.tpu(i)`` resolves or raises, the
default context, the one cache-directory rule, one chip per local worker."""
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu import context as ctx_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Dev:
    def __init__(self, platform, id):
        self.platform, self.id = platform, id


def test_tpu_out_of_range_raises_instead_of_wrapping():
    import jax

    n = len(jax.local_devices())
    assert mx.tpu(n - 1).jax_device() == jax.local_devices()[n - 1]
    with pytest.raises(mx.MXNetError, match="out of range"):
        mx.tpu(n).jax_device()
    with pytest.raises(mx.MXNetError, match="out of range"):
        mx.gpu(-1).jax_device()


def test_tpu_without_accelerator_raises_unless_host_was_forced(monkeypatch):
    import jax

    # un-forced platform, host devices only: no chip, so no tpu(0)
    monkeypatch.setattr(ctx_mod, "_forced_to_host", lambda: False)
    monkeypatch.setattr(jax, "local_devices",
                        lambda *a, **k: [_Dev("cpu", 0), _Dev("cpu", 1)])
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        mx.tpu(0).jax_device()
    assert mx.num_tpus() == 0 and mx.num_gpus() == 0


def test_chips_win_over_host_devices_and_set_the_default(monkeypatch):
    import jax

    chips = [_Dev("tpu", 0), _Dev("tpu", 1)]
    monkeypatch.setattr(jax, "local_devices",
                        lambda *a, **k: [_Dev("cpu", 0)] if k.get("backend")
                        == "cpu" else chips)
    assert mx.tpu(1).jax_device() is chips[1]
    assert mx.num_tpus() == 2
    assert mx.cpu(0).jax_device().platform == "cpu"
    assert mx.current_context() == mx.tpu(0)
    with mx.cpu():
        assert mx.current_context() == mx.cpu(0)
    assert mx.current_context() == mx.tpu(0)
    with pytest.raises(mx.MXNetError, match="out of range"):
        mx.tpu(2).jax_device()


def test_default_context_is_the_host_without_a_chip():
    assert mx.current_context() == mx.cpu(0)
    assert mx.mod.Module(mx.sym.Variable("data"), label_names=None) \
        ._context == [mx.cpu(0)]


def test_setitem_keeps_the_array_where_it_lived():
    import numpy as np

    a = mx.nd.zeros((2, 3), mx.cpu(3))
    dev = a.context.jax_device()
    a[:] = np.ones((2, 3), "f")
    assert a._data.devices() == {dev} and a._data.committed
    a[0] = 5.0
    assert a._data.devices() == {dev}


_CACHE_PROBE = ("import mxnet_tpu, jax; "
                "print('DIR=%s' % jax.config.jax_compilation_cache_dir)")


def _cache_dir_in_subprocess(**env_changes):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env.update({k: v for k, v in env_changes.items() if v is not None})
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return [l for l in proc.stdout.splitlines()
            if l.startswith("DIR=")][-1][4:]


@pytest.mark.parametrize("case", ["variable_set", "unset", "forced_cpu"])
def test_compile_cache_directory_rule(case, tmp_path):
    """Set: JAX reads the variable itself, the code sets nothing.  Unset:
    <checkout>/.jax_cache, also with JAX_PLATFORMS unset.  Forced to the
    host: off (XLA:CPU entries are not portable).  Acts at import, so each
    case gets a subprocess (importing initialises no backend)."""
    if case == "variable_set":
        want = str(tmp_path / "placed")
        got = _cache_dir_in_subprocess(JAX_COMPILATION_CACHE_DIR=want)
        assert got == want
        assert not os.path.exists(os.path.join(REPO, ".jax_cache", "placed"))
    elif case == "unset":
        assert _cache_dir_in_subprocess() == os.path.join(REPO, ".jax_cache")
    else:
        assert _cache_dir_in_subprocess(JAX_PLATFORMS="cpu") == "None"


def test_no_code_path_sets_a_cache_dir_when_the_variable_is_set():
    import re

    hits = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "mxnet_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn)) as f:
                    if re.search(r"jax_compilation_cache_dir", f.read()):
                        hits.append(os.path.relpath(os.path.join(root, fn),
                                                    REPO))
    assert hits == ["mxnet_tpu/__init__.py"]


def test_launcher_gives_each_local_worker_its_own_chip():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import launch

    chips = ["/dev/accel0", "/dev/accel1", "/dev/accel2", "/dev/accel3"]
    envs = [launch.chip_env({"A": "b"}, i, 4, chips) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" and e["A"] == "b"
               for e in envs)
    # nothing to assign: no chips, platform forced to the host, the
    # caller's own assignment, or a single worker that keeps every chip
    assert launch.chip_env({}, 0, 4, []) == {}
    assert launch.chip_env({"JAX_PLATFORMS": "cpu"}, 1, 4, chips) \
        == {"JAX_PLATFORMS": "cpu"}
    assert launch.chip_env({"TPU_VISIBLE_CHIPS": "2"}, 0, 4, chips) \
        == {"TPU_VISIBLE_CHIPS": "2"}
    assert launch.chip_env({}, 0, 1, chips) == {}
    with pytest.raises(SystemExit, match="one process at a time"):
        launch.chip_env({}, 0, 5, chips)


def test_launcher_parent_stays_off_the_backend():
    """``--metrics-port`` imports mxnet_tpu in the launcher; that must not
    initialise a backend (the parent would hold the chips)."""
    code = ("import sys; sys.argv = ['launch.py', '-n', '1', "
            "'--metrics-port', '%d', sys.executable, '-c', 'pass']; "
            "sys.path.insert(0, 'tools'); import launch\n"
            "try:\n    launch.main()\nexcept SystemExit as e:\n"
            "    assert not e.code, e.code\n"
            "import jax; from jax._src import xla_bridge as xb\n"
            "assert not xb.backends_are_initialized(), 'backend touched'")
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    proc = subprocess.run([sys.executable, "-c", code % port], cwd=REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
