"""chip_smoke.py: its body at toy size on the CPU mesh, its refusal to run
without a chip, and the compile-cache helper it shares with
__graft_entry__.py."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def chip_smoke():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke as module

        yield module
    finally:
        sys.path.remove(REPO)


def test_body_at_tiny_size_on_the_cpu_mesh(chip_smoke):
    """Save under fsdp=4, restore under fsdp=2 x model=2; the body itself
    requires bit-equal leaves, the per-device share after init and after
    restore, and resumed losses that continue the uninterrupted run."""
    from torchsnapshot_tpu.models import LlamaConfig

    result = chip_smoke.run_smoke(
        LlamaConfig.tiny(), jax.devices()[:4], seq_len=32
    )
    assert result["mesh"] == {
        "save_fsdp_model": [4, 1],
        "restore_fsdp_model": [2, 2],
    }
    assert result["staging_mode"] in ("pinned_host", "device")
    assert result["native_library"].endswith("libtpusnap.so")
    assert result["leaves"] == 38
    share = result["state_bytes"] / 4
    for when in ("after_init", "after_restore"):
        assert result["fullest_device_state_bytes"][when] <= share * 1.02 + (64 << 10)
    assert len(result["losses"]["resumed"]) == 3
    # Another layout sums in another order, so the check is a tolerance; it
    # must still tell one step from the next.
    assert result["loss_check"] == "rtol=0.001"
    assert result["loss_rel_diff"] <= 1e-3 < result["loss_step_rel_change"]
    json.dumps(result)  # the result line must serialise


def test_body_on_one_device_resumes_with_equal_losses(chip_smoke):
    """One device is what the driver's chip run has: same layout on both
    sides, so the resumed losses must EQUAL the uninterrupted run's."""
    from torchsnapshot_tpu.models import LlamaConfig

    result = chip_smoke.run_smoke(
        LlamaConfig.tiny(), jax.devices()[:1], seq_len=32
    )
    assert result["loss_check"] == "equal" and result["loss_rel_diff"] == 0.0
    assert result["losses"]["resumed"] == result["losses"]["uninterrupted"][2:]


def test_a_fallback_on_the_path_fails_the_body(chip_smoke, monkeypatch):
    """A staging downgrade is a production fallback the library keeps; on
    the smoke's path it must fail the run, not pass with a slower mode."""
    from torchsnapshot_tpu import device_staging
    from torchsnapshot_tpu.models import LlamaConfig

    def boom(arrays):
        raise RuntimeError("pretend the pinned_host reshard failed")

    device_staging.reset_pinned_host_health()
    monkeypatch.setattr(device_staging, "_pinned_host_copy_batch", boom)
    try:
        with pytest.raises(AssertionError, match="staging_downgrade"):
            chip_smoke.run_smoke(
                LlamaConfig.tiny(), jax.devices()[:2], seq_len=32
            )
    finally:
        device_staging.reset_pinned_host_health()


def test_a_library_warning_on_the_path_fails_the_body(chip_smoke, monkeypatch):
    """A failed batched upload (what an HBM OOM looks like) is retried array
    by array and the restore still comes out bit-equal; the only trace is a
    warning, and on the smoke's path that fails the run."""
    from torchsnapshot_tpu.models import LlamaConfig

    real = jax.device_put
    failed = []

    def flaky(x, *args, **kwargs):
        # Host buffers going up: the restore's batch, not the save's copies.
        if isinstance(x, list) and isinstance(x[0], np.ndarray) and not failed:
            failed.append(len(x))
            raise RuntimeError("RESOURCE_EXHAUSTED: pretend")
        return real(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", flaky)
    with pytest.raises(AssertionError, match="library warning.*batched device_put"):
        chip_smoke.run_smoke(LlamaConfig.tiny(), jax.devices()[:1], seq_len=32)
    assert failed


def test_cut_fits_one_v5e_chip_in_bf16_and_four_in_fp32(chip_smoke):
    hbm = 16909336064  # a TPU v5 lite's bytes_limit
    one = chip_smoke.choose_cut(1, hbm)
    four = chip_smoke.choose_cut(4, hbm)
    for cfg in (one, four):
        assert (cfg.d_model, cfg.d_ff, cfg.vocab_size) == (4096, 14336, 128256)
        assert (cfg.n_heads, cfg.n_kv_heads) == (32, 8)
    assert jax.numpy.dtype(one.param_dtype) == jax.numpy.bfloat16
    assert jax.numpy.dtype(four.param_dtype) == jax.numpy.float32
    assert 1 <= one.n_layers <= four.n_layers <= 4
    with pytest.raises(RuntimeError, match="fits"):
        chip_smoke.choose_cut(1, 4 << 30)


def test_last_line_is_the_drivers_contract_and_nothing_more(chip_smoke, capsys):
    """The driver parses the last line of stdout and refuses anything but
    exactly ``ok`` and ``device`` (``platform``, ``kind``, ``count``); what
    else the run learned goes on the ``result`` line before it."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    assert chip_smoke.report(device, lambda: {"staging_mode": "pinned_host"}) == 0
    *_, detail, last = capsys.readouterr().out.splitlines()
    assert json.loads(last) == {"ok": True, "device": device}
    assert json.loads(detail.removeprefix("[chip_smoke] result ")) == {
        "staging_mode": "pinned_host"
    }

    def failing_phase():
        raise AssertionError("a leaf is not bit-equal after restore")

    assert chip_smoke.report(device, failing_phase) != 0
    captured = capsys.readouterr()
    assert json.loads(captured.out.splitlines()[-1]) == {"ok": False, "device": device}
    assert "not bit-equal" in captured.err


def _run(cmd, env_extra, cwd=REPO):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    return subprocess.run(
        cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=120
    )


def test_script_refuses_to_run_without_a_chip():
    proc = _run([sys.executable, "chip_smoke.py"], {})
    assert proc.returncode not in (0, None)
    assert "no accelerator" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    the script must fail, not find the library somewhere else."""
    with open(os.path.join(REPO, "chip_smoke.py")) as src:
        (tmp_path / "chip_smoke.py").write_text(src.read())
    proc = _run(
        [sys.executable, "chip_smoke.py"], {"PYTHONPATH": ""}, cwd=str(tmp_path)
    )
    assert proc.returncode != 0
    assert "torchsnapshot_tpu" in proc.stderr
    assert proc.stdout.strip() == ""


_CACHE_PROBE = (
    "import os, jax;"
    "from torchsnapshot_tpu.utils import compile_cache as c;"
    "d = c.place_compile_cache();"
    "print(d); print(jax.config.jax_compilation_cache_dir);"
    "print(c.CHECKOUT_CACHE_DIR)"
)


def test_compile_cache_follows_the_environment_variable(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the code sets nothing: JAX's own
    reading of the variable is what the config shows, and a checkout with no
    cache directory still has none."""
    import shutil

    checkout = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(REPO, "torchsnapshot_tpu"),
        checkout / "torchsnapshot_tpu",
        ignore=shutil.ignore_patterns("__pycache__", "*.so"),
    )
    placed = str(tmp_path / "elsewhere")
    proc = _run(
        [sys.executable, "-c", _CACHE_PROBE + ";jax.jit(lambda x: x + 1)(1.0)"],
        {"JAX_COMPILATION_CACHE_DIR": placed, "PYTHONPATH": str(checkout)},
        cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, configured, in_checkout = proc.stdout.split()
    assert returned == configured == placed
    assert in_checkout == str(checkout / ".jax_compile_cache")
    assert not os.path.exists(in_checkout)


def test_compile_cache_is_one_fixed_path_under_the_checkout():
    """Unset, two separate processes (started from different directories)
    resolve the same path: the checkout's, never a temporary or per-process
    name, since the path is part of the cache key."""
    outs = []
    for cwd in (REPO, os.path.dirname(REPO)):
        proc = _run(
            [sys.executable, "-c", _CACHE_PROBE], {"PYTHONPATH": REPO}, cwd=cwd
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(proc.stdout.split())
    assert outs[0] == outs[1]
    returned, configured, in_checkout = outs[0]
    assert returned == configured == in_checkout
    assert in_checkout == os.path.join(REPO, ".jax_compile_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()
