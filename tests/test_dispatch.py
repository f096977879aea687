"""Kernel dispatch + autotune subsystem (kernels/dispatch.py, autotune.py).

The load-bearing contract: every backend computes the *same* codes — the
Pallas interpret backend (the TPU kernel body, evaluated on CPU) must be
bit-identical to the pure-XLA reference for all three rounding schemes in
both pulse formats, and the fused matmuls must agree to float tolerance.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.matmul import quantized_matmul
from repro.kernels import autotune, dispatch, ref
from repro.numerics.policy import QuantPolicy, qmatmul

SCHEMES = ["deterministic", "stochastic", "dither"]
FORMATS = ["unary", "spread"]


# ---------------------------------------------------------------------------
# backend parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("scheme", SCHEMES)
def test_quantize_codes_bit_identical_across_backends(scheme, fmt):
    x = jax.random.uniform(jax.random.PRNGKey(0), (48, 96), minval=-1, maxval=1)
    kw = dict(bits=8, lo=-1.0, hi=1.0, scheme=scheme, counter=7, seed=3,
              n_pulses=16, fmt=fmt)
    codes_ref = dispatch.quantize(x, backend="xla-ref", **kw)
    codes_pal = dispatch.quantize(x, backend="pallas-interpret",
                                  block=(32, 32), **kw)
    assert jnp.array_equal(codes_ref, codes_pal)


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("scheme", SCHEMES)
def test_matmul_outputs_match_across_backends(scheme, fmt):
    a = jax.random.uniform(jax.random.PRNGKey(1), (33, 64))
    b = jax.random.uniform(jax.random.PRNGKey(2), (64, 50), minval=-1, maxval=1)
    kw = dict(bits=6, scheme=scheme, counter=2, seed=9,
              a_range=(0.0, 1.0), b_range=(-1.0, 1.0), fmt=fmt)
    out_ref = dispatch.matmul(a, b, backend="xla-ref", **kw)
    out_pal = dispatch.matmul(a, b, backend="pallas-interpret",
                              block=(32, 32, 32), **kw)
    assert float(jnp.max(jnp.abs(out_ref - out_pal))) < 1e-4


def test_unary_and_spread_formats_differ_but_both_unbiased():
    """The two σ formats are different permutations of the same pulses:
    codes differ at fixed counter, but averaging over a full period
    recovers x for both (§VII time-averaged unbiasedness)."""
    x = jax.random.uniform(jax.random.PRNGKey(3), (32, 32))
    n = 16
    per_fmt = {}
    for fmt in FORMATS:
        codes = [dispatch.quantize(x, bits=4, scheme="dither", counter=c,
                                   n_pulses=n, fmt=fmt, backend="xla-ref")
                 for c in range(n)]
        per_fmt[fmt] = codes
        mean = jnp.stack(codes).astype(jnp.float32).mean(0) / 15.0
        assert float(jnp.max(jnp.abs(mean - x))) < 0.1
    assert not all(
        bool(jnp.array_equal(a, b))
        for a, b in zip(per_fmt["unary"], per_fmt["spread"])
    )


# ---------------------------------------------------------------------------
# selection / override
# ---------------------------------------------------------------------------


def test_registry_lists_builtin_backends():
    names = dispatch.available_backends()
    for expected in ("pallas-tpu", "pallas-interpret", "xla-ref"):
        assert expected in names


def test_resolve_platform_default_and_aliases():
    on_tpu = jax.default_backend() == "tpu"
    assert dispatch.resolve_backend().name == (
        "pallas-tpu" if on_tpu else dispatch.DEFAULT_CPU_BACKEND)
    assert dispatch.resolve_backend("pallas").name == (
        "pallas-tpu" if on_tpu else "pallas-interpret")
    assert dispatch.resolve_backend("ref").name == "xla-ref"
    with pytest.raises(ValueError, match="unknown kernel backend"):
        dispatch.resolve_backend("nonesuch")


def test_explicit_pallas_tpu_needs_a_tpu(monkeypatch):
    """Naming the compiled backend off-TPU fails at resolution with an
    error that says so, not deep inside Mosaic lowering."""
    if jax.default_backend() == "tpu":
        assert dispatch.resolve_backend("pallas-tpu").name == "pallas-tpu"
        return
    with pytest.raises(RuntimeError, match="compiles for a TPU"):
        dispatch.resolve_backend("pallas-tpu")
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas-tpu")
    with pytest.raises(RuntimeError, match="compiles for a TPU"):
        dispatch.quantize(jax.numpy.zeros((8, 128)), bits=8)


def test_env_override(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_VAR, "pallas-interpret")
    assert dispatch.resolve_backend().name == "pallas-interpret"
    # 'auto' defers to the environment too — QuantPolicy.resolved passes it
    # explicitly, and the env var must still redirect policy call sites
    assert dispatch.resolve_backend("auto").name == "pallas-interpret"
    assert (QuantPolicy(scheme="dither", backend="auto").resolved().backend
            == "pallas-interpret")
    # an explicit concrete backend beats the environment
    assert dispatch.resolve_backend("xla-ref").name == "xla-ref"


def test_policy_backend_resolution():
    assert dispatch.resolve_policy_backend("jnp") == "jnp"
    resolved = QuantPolicy(scheme="dither", backend="auto").resolved()
    assert resolved.backend in dispatch.available_backends()


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------


def test_candidates_respect_vmem_budget():
    budget = autotune.VMEM_BUDGET_BYTES
    cands = autotune.matmul_candidates(4096, 8192, 4096)
    assert cands
    for blk in cands:
        assert autotune.matmul_vmem_bytes(blk) <= budget
    # model pick = a candidate, and usable for real shapes
    blk = autotune.best_block("matmul", (4096, 8192, 4096), "float32", 8,
                              "dither", "pallas-tpu")
    assert blk in cands


def test_best_block_small_shapes_stay_runnable():
    blk = autotune.best_block("matmul", (32, 64, 48), "float32", 8, "dither",
                              "pallas-interpret")
    out = dispatch.matmul(
        jax.random.uniform(jax.random.PRNGKey(4), (32, 64)),
        jax.random.uniform(jax.random.PRNGKey(5), (64, 48)),
        bits=8, block=blk, backend="pallas-interpret")
    assert out.shape == (32, 48)


def test_measured_sweep_caches_winner(tmp_path, monkeypatch):
    cache_file = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache_file))
    autotune.clear_cache()
    a = jax.random.uniform(jax.random.PRNGKey(6), (32, 32))
    b = jax.random.uniform(jax.random.PRNGKey(7), (32, 32))

    def run(block):
        return dispatch.matmul(a, b, bits=8, scheme="dither",
                               block=tuple(block), backend="pallas-interpret")

    winner, results = autotune.autotune_matmul(
        32, 32, 32, bits=8, scheme="dither", backend="pallas-interpret",
        run=run, repeats=1, candidates=[(32, 32, 32), (16, 16, 16)])
    assert len(results) == 2
    assert tuple(results[0]["block"]) == winner

    # persisted and re-loaded: best_block now returns the measured winner
    assert json.loads(cache_file.read_text())
    autotune.clear_cache()
    got = autotune.best_block("matmul", (32, 32, 32), "float32", 8, "dither",
                              "pallas-interpret")
    assert got == winner
    autotune.clear_cache()


def test_paged_attention_candidates_and_model_pick():
    """(bs,) pool-block candidates stay under the VMEM budget; the model
    pick balances granularity — a full-length request spans ≥ 4 blocks
    whenever a candidate allows it, and tiny caps stay servable."""
    cands = autotune.paged_attention_candidates(4096, nkv=8, hd=64, group=4,
                                                quantized=True)
    assert cands
    for (bs,) in cands:
        assert autotune.decode_attention_vmem_bytes(
            (bs,), nkv=8, hd=64, group=4, quantized=True) \
            <= autotune.VMEM_BUDGET_BYTES
    pick = autotune.best_block("paged_attention", (8, 4096, 8, 4, 64),
                               "int8", 8, "flash", "pallas-tpu")
    assert pick in cands and pick[0] * 4 <= 4096
    tiny = autotune.best_block("paged_attention", (2, 8, 2, 2, 32),
                               "bfloat16", 16, "flash", "pallas-interpret")
    assert 1 <= tiny[0] <= 8


def test_save_cache_atomic_merge_survives_concurrent_writers(tmp_path,
                                                             monkeypatch):
    """The winner-cache write is merge + atomic rename: entries persisted
    by another process survive, ours win on conflicts, no temp files are
    left behind, and the file is never observable half-written (satellite:
    parallel bench/CI runs must not truncate each other)."""
    cache_file = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache_file))
    autotune.clear_cache()
    # "another process" wrote first
    cache_file.write_text(json.dumps({"matmul|1x1x1|f32|8|dither|x": [4, 4, 4],
                                      "shared|key": [1]}))
    autotune._CACHE["shared|key"] = (2,)
    autotune._CACHE["quantize|8x8|f32|8|dither|x"] = (8, 8)
    autotune.save_cache()
    merged = json.loads(cache_file.read_text())
    assert merged["matmul|1x1x1|f32|8|dither|x"] == [4, 4, 4]  # theirs kept
    assert merged["shared|key"] == [2]                         # ours wins
    assert merged["quantize|8x8|f32|8|dither|x"] == [8, 8]
    assert not list(tmp_path.glob("*.tmp.*"))                  # swap cleaned up
    autotune.clear_cache()


# ---------------------------------------------------------------------------
# call-site wiring
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", SCHEMES)
def test_quantized_matmul_separate_backend_parity(scheme):
    a = jax.random.uniform(jax.random.PRNGKey(8), (24, 32))
    b = jax.random.uniform(jax.random.PRNGKey(9), (32, 20))
    c_ref = quantized_matmul(a, b, bits=8, scheme=scheme, variant="separate",
                             backend="xla-ref")
    c_pal = quantized_matmul(a, b, bits=8, scheme=scheme, variant="separate",
                             backend="pallas-interpret")
    assert float(jnp.max(jnp.abs(c_ref - c_pal))) < 1e-4


def test_qmatmul_fused_backend_matches_unfused():
    """The policy's fused dispatcher path lands on the same quantisation
    grid as the unfused fake-quant path (different pulse counts → different
    draws, but both within the same quantisation error of x@w)."""
    x = jax.random.uniform(jax.random.PRNGKey(10), (16, 32), minval=-1, maxval=1)
    w = jax.random.uniform(jax.random.PRNGKey(11), (32, 8), minval=-1, maxval=1)
    tol = 32 * (2.0 / 255) * 2  # K × grid step, generous
    exact = x @ w
    for backend in ["jnp", "xla-ref", "pallas-interpret"]:
        pol = QuantPolicy(scheme="dither", bits=8, backend=backend)
        out = qmatmul(x, w, pol, 0, jnp.float32(3))
        assert float(jnp.max(jnp.abs(out - exact))) < tol, backend


def test_qmatmul_fused_ste_gradients():
    pol = QuantPolicy(scheme="dither", bits=8, backend="xla-ref")
    x = jax.random.uniform(jax.random.PRNGKey(12), (8, 16))
    w = jax.random.uniform(jax.random.PRNGKey(13), (16, 4))
    gx, gw = jax.grad(
        lambda x, w: jnp.sum(qmatmul(x, w, pol, 0, jnp.float32(0))),
        argnums=(0, 1))(x, w)
    assert jnp.allclose(gx, jnp.ones((8, 4)) @ w.T, rtol=1e-5, atol=1e-6)
    assert jnp.allclose(gw, x.T @ jnp.ones((8, 4)), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("variant", ["separate", "round_a_once", "per_partial"])
def test_quantized_matmul_counter_advances_all_variants(variant):
    """The global step counter i_s phase-shifts every variant ("rounding in
    time"), not just the dispatcher-backed separate path."""
    a = jax.random.uniform(jax.random.PRNGKey(16), (12, 16))
    b = jax.random.uniform(jax.random.PRNGKey(17), (16, 8))
    c0 = quantized_matmul(a, b, bits=3, scheme="dither", variant=variant,
                          counter=0)
    c1 = quantized_matmul(a, b, bits=3, scheme="dither", variant=variant,
                          counter=1)
    assert float(jnp.max(jnp.abs(c0 - c1))) > 0.0


def test_matmul_counter_advances_on_every_backend():
    a = jax.random.uniform(jax.random.PRNGKey(14), (32, 32))
    b = jax.random.uniform(jax.random.PRNGKey(15), (32, 32))
    for backend in ["xla-ref", "pallas-interpret"]:
        c0 = dispatch.matmul(a, b, bits=3, scheme="dither", counter=0,
                             block=(32, 32, 32), backend=backend)
        c1 = dispatch.matmul(a, b, bits=3, scheme="dither", counter=1,
                             block=(32, 32, 32), backend=backend)
        assert float(jnp.max(jnp.abs(c0 - c1))) > 0.0, backend
