"""Start-up rules of the device path (kme_tpu/_jaxsetup.py and its
callers): which backend, where the compile cache lives, what the served
path refuses instead of falling back — and that the three served kernel
shapes still lower for the TPU, so a kernel edit that leaks i64 fails
here and not on the chip."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = r"""
import json, sys
import jax
from kme_tpu import _jaxsetup
out = {"cache_dir": _jaxsetup.cache_dir()}
if "--backend" in sys.argv:
    decide = _jaxsetup.backend.__wrapped__      # past the lru_cache
    jax.config.update("jax_platforms", "cpu")
    out["cpu"] = [decide(), _jaxsetup.interpret()]
    jax.config.update("jax_platforms", "rocm")
    try:
        decide()
    except RuntimeError as e:
        out["unknown"] = str(e)
    jax.config.update("jax_platforms", "")
    try:
        out["unset"] = decide()                  # a machine with a TPU
    except RuntimeError as e:
        from jax._src import xla_bridge
        out["unset_error"] = str(e)
        out["jax_says"] = xla_bridge._backend_errors.get(
            "tpu", "no TPU plug-in found")
print(json.dumps(out))
"""


def _probe(cwd, *args, **env_changes):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    env.update(env_changes)
    r = subprocess.run([sys.executable, "-c", _PROBE, *args], cwd=cwd,
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_backend_rule_and_default_cache_dir(tmp_path):
    out = _probe(str(tmp_path), "--backend")
    # explicit cpu -> the CPU under the interpreter
    assert out["cpu"] == ["cpu", True]
    # a platform there are no kernels for -> raises, naming it
    assert "rocm" in out["unknown"]
    # nothing asked for and no TPU -> raises with JAX's own reason
    if "unset" in out:
        pytest.skip("this machine has a TPU")
    assert out["jax_says"] in out["unset_error"]
    assert "JAX_PLATFORMS=cpu" in out["unset_error"]
    # the cache: derived from the package's path, not the cwd
    assert out["cache_dir"] == os.path.join(REPO, ".jax_cache")
    other = tmp_path / "elsewhere"
    other.mkdir()
    assert _probe(str(other))["cache_dir"] == out["cache_dir"]


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    want = str(tmp_path / "cc")
    out = _probe(str(tmp_path), JAX_COMPILATION_CACHE_DIR=want)
    assert out["cache_dir"] == want


def test_backend_in_process_is_the_requested_cpu():
    from kme_tpu import _jaxsetup

    assert _jaxsetup.backend() == "cpu" and _jaxsetup.interpret()


def test_serve_refuses_the_sweep_engine_and_its_flags(capsys):
    """`--engine lanes` (removed in PR 54) and the two flags only it
    read are argparse errors: exit 2, and the message names the
    engines there are."""
    from kme_tpu.bridge.serve import build_parser

    with pytest.raises(SystemExit) as e:
        build_parser().parse_args(["--engine", "lanes"])
    assert e.value.code == 2
    assert "--engine {seq,oracle,native}" in capsys.readouterr().err
    for flag in ("--width", "--shards"):
        with pytest.raises(SystemExit) as e:
            build_parser().parse_args([flag, "1"])
        assert e.value.code == 2


def test_the_service_refuses_the_sweep_engine():
    from kme_tpu.bridge.broker import InProcessBroker
    from kme_tpu.bridge.service import MatchService

    with pytest.raises(ValueError, match="unknown engine 'lanes'"):
        MatchService(InProcessBroker(), engine="lanes")


def test_the_services_default_engine_is_kme_serves():
    """One decision in one place: a caller of the class and a caller of
    the command line get the same engine."""
    import inspect

    from kme_tpu.bridge.serve import build_parser
    from kme_tpu.bridge.service import MatchService

    default = inspect.signature(MatchService).parameters["engine"].default
    assert default == build_parser().get_default("engine") == "seq"


def test_require_library_raises_unless_disabled(monkeypatch):
    from kme_tpu import native

    monkeypatch.setattr(native, "load_library", lambda: None)
    monkeypatch.delenv("KME_NATIVE", raising=False)
    with pytest.raises(RuntimeError, match="KME_NATIVE=0"):
        native.require_library()
    monkeypatch.setenv("KME_NATIVE", "0")
    assert native.require_library() is None


def test_restore_failure_is_not_a_fresh_start(tmp_path, monkeypatch):
    """load_seq_session skips a snapshot it cannot READ; a failure while
    putting a readable one on the device must surface (it used to print
    'skipping unreadable snapshot' and start over at offset 0)."""
    from kme_tpu.engine import seq as SQ
    from kme_tpu.runtime import checkpoint as ck
    from kme_tpu.runtime.seqsession import SeqSession

    cfg = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=8,
                       batch=128, pos_cap=1 << 10, fill_cap=1 << 10,
                       probe_max=8)
    ck.save_seq_session(str(tmp_path), SeqSession(cfg), 7)
    ses, off = ck.load_seq_session(str(tmp_path), cfg)
    assert ses is not None and off == 7

    def boom(*_a, **_k):
        raise RuntimeError("RESOURCE_EXHAUSTED: device")

    monkeypatch.setattr(SQ, "import_canonical", boom)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        ck.load_seq_session(str(tmp_path), cfg)


# the three Mosaic programs chip_smoke.py serves: A fixed + hbm_books,
# C java, D kme-serve's default shape (books resident in VMEM)
_SERVED = {
    "A": dict(lanes=1024, slots=8192, accounts=2048, max_fills=16,
              hbm_books=True, compat="fixed"),
    "C": dict(lanes=8, slots=8192, accounts=128, max_fills=128,
              hbm_books=True, compat="java"),
    "D": dict(lanes=1024, slots=128, accounts=4096, max_fills=16,
              hbm_books=False, compat="fixed"),
}


@pytest.mark.parametrize("name", sorted(_SERVED))
def test_served_kernels_lower_for_tpu(name, monkeypatch):
    import jax
    import numpy as np

    from kme_tpu import _jaxsetup
    from kme_tpu.engine import seq as SQ

    cfg = SQ.SeqConfig(**_SERVED[name])
    monkeypatch.setattr(_jaxsetup, "interpret", lambda: False)
    _, raw_call = SQ.build_seq_step.__wrapped__(cfg)   # not the lru_cache
    state = jax.eval_shape(lambda: SQ.make_seq_state(cfg))   # no 400 MB
    cols = {k: np.zeros(0, np.int64) for k in (
        "act", "aid", "price", "size", "lane", "oid", "aid_raw",
        "sid_raw", "flags")}
    msgs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in SQ.pack_msgs(cfg, cols, 0).items()}
    text = jax.jit(raw_call).trace(state, msgs).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
