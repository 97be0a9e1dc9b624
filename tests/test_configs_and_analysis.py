"""Registry exactness vs the assignment + HLO analyzer unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.launch import hlo_analysis

# (layers, d_model, heads, kv_heads, d_ff, vocab) from the assignment table.
ASSIGNED = {
    "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
    "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
    "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
    "mistral-nemo-12b": (40, 5120, 32, 8, 14336, 131072),
    "granite-20b": (52, 6144, 48, 1, 24576, 49152),
    "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
    "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400),
    "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
    "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
    "mamba2-780m": (48, 1536, None, None, 0, 50280),
}


def test_all_assigned_archs_present():
    assert set(registry.ARCHS) == set(ASSIGNED)


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_exact_assigned_numbers(arch):
    cfg = registry.get(arch)
    L, d, h, kv, dff, vocab = ASSIGNED[arch]
    assert cfg.n_layers == L
    assert cfg.d_model == d
    if h is not None:
        assert cfg.n_heads == h
        assert cfg.n_kv_heads == kv
    assert cfg.d_ff == dff
    assert cfg.vocab_size == vocab
    assert cfg.citation


def test_family_specifics():
    ds = registry.get("deepseek-v2-236b")
    assert ds.mla and ds.kv_lora_rank == 512 and ds.n_experts == 160
    assert ds.top_k == 6 and ds.n_shared_experts == 2
    qm = registry.get("qwen2-moe-a2.7b")
    assert qm.n_experts == 60 and qm.top_k == 4 and qm.n_shared_experts == 4
    m2 = registry.get("mamba2-780m")
    assert m2.ssm_state == 128
    rg = registry.get("recurrentgemma-9b")
    assert rg.block_pattern == ("rec", "rec", "attn")
    q3 = registry.get("qwen3-1.7b")
    assert q3.qk_norm
    q2 = registry.get("qwen2-1.5b")
    assert q2.qkv_bias


def test_shapes_table():
    s = registry.SHAPES
    assert (s["train_4k"].seq_len, s["train_4k"].global_batch) == (4096, 256)
    assert (s["prefill_32k"].seq_len, s["prefill_32k"].global_batch) == (32768, 32)
    assert (s["decode_32k"].seq_len, s["decode_32k"].global_batch) == (32768, 128)
    assert (s["long_500k"].seq_len, s["long_500k"].global_batch) == (524288, 1)


def test_long_ctx_policy():
    whisper = registry.get("whisper-tiny")
    assert not registry.supported(whisper, registry.SHAPES["long_500k"])
    dense = registry.get("mistral-nemo-12b")
    adj = registry.for_shape(dense, registry.SHAPES["long_500k"])
    assert adj.sliding_window == registry.LONG_CTX_WINDOW
    ssm = registry.get("mamba2-780m")
    assert registry.for_shape(ssm, registry.SHAPES["long_500k"]).sliding_window is None


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------

def test_analyzer_counts_scan_flops():
    """Loop-aware FLOPs == trips x per-iteration dot flops (single device)."""
    a = jnp.ones((64, 64), jnp.float32)

    def f(x):
        def body(c, _):
            return jnp.tanh(c @ a), None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out.sum()

    compiled = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    costs = hlo_analysis.analyze_text(compiled.as_text())
    expected = 5 * 2 * 64 * 64 * 64
    assert abs(costs.flops - expected) / expected < 0.05, costs.flops


def test_analyzer_counts_fusion_dots():
    def f(x, y):
        return (jnp.tanh(x @ y) * 2.0).sum()

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((32, 48), jnp.float32),
        jax.ShapeDtypeStruct((48, 16), jnp.float32),
    ).compile()
    costs = hlo_analysis.analyze_text(compiled.as_text())
    expected = 2 * 32 * 48 * 16
    assert abs(costs.flops - expected) / expected < 0.05


def test_analyzer_hbm_bytes_reasonable():
    def f(x):
        return (x * 2.0).sum()

    n = 1 << 16
    compiled = jax.jit(f).lower(jax.ShapeDtypeStruct((n,), jnp.float32)).compile()
    costs = hlo_analysis.analyze_text(compiled.as_text())
    assert costs.hbm_bytes >= 4 * n  # at least reads the input


def test_model_flops_formula():
    from repro.launch import roofline

    assert roofline.model_flops(10, 0, 5, "train") == 6 * 10 * 5
    assert roofline.model_flops(10, 4, 5, "serve") == 2 * 4 * 5


def test_roofline_dominant_term():
    from repro.launch.roofline import Roofline

    r = Roofline(
        device_kind="TPU v5 lite",
        chips=256, flops_per_device=197e12, bytes_per_device=819e9 * 2,
        collective_per_device=0, peak_memory_per_device=0,
        collective_breakdown={},
    )
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(2.0)
    assert r.dominant == "memory"


def test_roofline_unknown_device_is_an_error():
    """Peaks are keyed by device_kind; a device without published peaks is
    refused, never measured against another chip's roof."""
    from repro.launch import roofline

    assert roofline.peaks("TPU v5 lite").flops == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("cpu")
    r = roofline.Roofline(
        device_kind="cpu", chips=1, flops_per_device=1.0,
        bytes_per_device=1.0, collective_per_device=0.0,
        peak_memory_per_device=0.0, collective_breakdown={},
    )
    with pytest.raises(ValueError, match="no published peaks"):
        r.compute_s


def test_compile_cache_honours_env_else_fixed_repo_path(monkeypatch):
    """Entry points keep JAX's persistent cache where the environment says,
    else at one fixed path inside the checkout — never a temporary name."""
    from pathlib import Path

    from repro.launch import compile_cache

    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.enable() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in calls
    monkeypatch.delenv(compile_cache.ENV_VAR)
    path = compile_cache.enable()
    repo = Path(__file__).resolve().parents[1]
    assert path == str(repo / ".jax_cache")
    assert calls["jax_compilation_cache_dir"] == path
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0
