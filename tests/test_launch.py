"""Launch-layer units: config resolution, depth calibration helpers,
input specs, mesh constants — all single-device testable."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_arch
from repro.launch.steps import (ShapeSkip, apply_opts, depth_counts,
                                resolve_config, with_depth)
from repro.models.model import build


def test_resolve_long500k_dense_uses_swa():
    cfg = resolve_config("internlm2-20b", "long_500k")
    assert cfg.sliding_window == 4096 and cfg.name.endswith("-swa")
    # ssm/hybrid archs stay native
    assert resolve_config("mamba2-780m", "long_500k").sliding_window == 0
    assert resolve_config("jamba-1.5-large-398b",
                          "long_500k").sliding_window == 0


def test_resolve_train_enables_remat():
    assert resolve_config("llama3.2-1b", "train_4k").remat
    assert not resolve_config("llama3.2-1b", "decode_32k").remat


def test_depth_counts_and_with_depth_roundtrip():
    for arch in ARCHS:
        cfg = ARCHS[arch]
        counts = depth_counts(cfg)
        shallow = with_depth(cfg, {k: 1 for k in counts})
        assert all(v == 1 for v in depth_counts(shallow).values())
        restored = with_depth(shallow, counts)
        assert restored.n_layers == cfg.n_layers
        if cfg.family == "encdec":
            assert restored.encoder.n_layers == cfg.encoder.n_layers


def test_with_depth_preserves_block_structure():
    cfg = ARCHS["jamba-1.5-large-398b"]
    one = with_depth(cfg, {"blocks": 1})
    assert one.n_layers == cfg.attn_every  # one full super-block


def test_input_specs_decode_cache_lengths():
    for arch, shape_name, expect_len in [
        ("llama3.2-1b", "decode_32k", 32_768),
        ("internlm2-20b", "long_500k", 4096),      # swa window cap
        ("jamba-1.5-large-398b", "long_500k", 524_288),
    ]:
        cfg = resolve_config(arch, shape_name)
        model = build(cfg)
        specs = model.input_specs(SHAPES[shape_name])
        ks = [l for p, l in
              jax.tree_util.tree_flatten_with_path(specs["cache"])[0]
              if str(p[-1].key) == "k" or str(getattr(p[-1], "key", "")) == "k"]
        if ks:
            assert ks[0].shape[2] == expect_len, (arch, ks[0].shape)


def test_decode_specs_are_one_token():
    for arch in ARCHS:
        for shape_name in ("decode_32k", "long_500k"):
            try:
                cfg = resolve_config(arch, shape_name)
            except ShapeSkip:
                continue
            model = build(cfg)
            specs = model.input_specs(SHAPES[shape_name])
            assert specs["token"].shape == (SHAPES[shape_name].global_batch,
                                            1)


def test_hw_constants_match_brief():
    from repro.launch.mesh import HW
    assert HW["peak_flops_bf16"] == 197e12
    assert HW["hbm_bandwidth"] == 819e9
    assert HW["ici_link_bandwidth"] == 50e9


def test_mesh_shapes():
    # make_production_mesh touches device state -> only verify the shape
    # logic via the documented contract (the dry-run exercises the real
    # thing in its own process)
    import inspect
    from repro.launch import mesh as mesh_mod
    src = inspect.getsource(mesh_mod.make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src


def test_ssd_chunk_padding_path():
    """SSD pads non-multiple sequence lengths; outputs must match an
    explicitly padded run."""
    from repro.models.ssm import init_ssm, ssm_block
    cfg = get_arch("mamba2-780m", variant="reduced")
    p = init_ssm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(2, 23, cfg.d_model)), jnp.float32)
    y, _ = ssm_block(p, u, cfg)
    assert y.shape == u.shape and bool(jnp.all(jnp.isfinite(y)))
    # prefix consistency: running the first 17 tokens alone gives the
    # same outputs (causality across the pad boundary)
    y2, _ = ssm_block(p, u[:, :17], cfg)
    np.testing.assert_allclose(np.asarray(y[:, :17]), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)


def test_compile_cache_set_by_env_is_left_alone(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and the
    helper configures no directory of its own."""
    from repro.launch import compile_cache as cc
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(cc.ENV, str(tmp_path))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    """Unset: one fixed path inside the checkout (the cache key includes
    the directory), the same on every call, and ignored by git."""
    from pathlib import Path
    from repro.launch import compile_cache as cc
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(cc.ENV, raising=False)
    repo = Path(__file__).resolve().parents[1]
    first, second = cc.enable_compile_cache(), cc.enable_compile_cache()
    assert first == second == str(repo / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    assert "/.jax_cache/" in (repo / ".gitignore").read_text().split()


def test_chip_smoke_fails_without_tpu(monkeypatch, tmp_path, capsys):
    """On the CPU backend the chip check exits non-zero and prints no
    result line — it never falls back to the CPU."""
    import chip_smoke
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax.default_backend() == "cpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok": true' not in out
    assert "no TPU" in err
