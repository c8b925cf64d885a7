"""CPU rehearsal of ``chip_smoke.py``: both phases at ``smoke()`` sizes,
the paged kernel in interpret mode, and the script's refusal to report
a result anywhere but on a TPU."""
import os
import subprocess
import sys
import textwrap

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402
from repro.configs import registry  # noqa: E402


def test_train_phase_at_smoke_size():
    out = chip_smoke.train_phase(registry.get_smoke_config("mamba2-130m"),
                                 batch=2, seq=32, steps=3, log=print)
    assert len(out["losses"]) == 3
    assert out["grads_finite"] == [1.0, 1.0, 1.0]
    assert out["ref_rel_err"] <= chip_smoke.LOSS_REL_TOL
    # one device holds the whole (unsharded) state
    assert list(out["state_bytes_per_device"].values()) == [
        out["state_bytes_total"]]


def test_serve_phase_at_smoke_size():
    out = chip_smoke.serve_phase(registry.get_smoke_config("starcoder2-3b"),
                                 n_slots=3, max_seq=128, page_size=16,
                                 chunk=16, n_requests=4, prompt_len=(5, 60),
                                 max_new=4, log=print)
    assert out["statuses"] == ["ok"] * 4
    assert out["n_tokens"] == [4] * 4
    assert out["logits"]["worst_ratio"] <= 1.0
    assert out["logits_full_depth"]["rel_rms"] <= chip_smoke.LOGIT_RMS_TOL
    # every format runs off-TPU (interpret mode), within the bound
    assert set(out["kernel_formats"]) == {"bf16", "i8", "f8_e4m3",
                                          "f8_e3m4"}
    # interpret mode lowers the kernel to plain XLA: no TPU custom call
    assert not out["tpu_custom_call"]


_MESH_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, ".")
    import chip_smoke
    from repro.configs import registry
    out = chip_smoke.mesh_train_phase(
        registry.get_smoke_config("mamba2-130m"), batch=4, seq=32, steps=2)
    per_dev = out["sharded"]["state_bytes_per_device"]
    assert len(per_dev) == 4, per_dev
    assert max(per_dev.values()) < out["sharded"]["state_bytes_total"]
    print("MESH_OK", out["max_rel_diff"])
""")


def test_mesh_train_phase_on_four_virtual_devices():
    """The ``--chips 4`` path on four CPU devices: state sharded over
    the (data=2, model=2) mesh, losses matching one device."""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                       capture_output=True, text=True, env=env, cwd=root,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "MESH_OK" in r.stdout


def test_main_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set;
    without it the cache is the fixed ``.jax_cache/`` of the checkout."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
