"""DeepSeek-V2-Lite under split LoRA fine-tuning, at tiny widths on the CPU:
the program's unit stack against the benchmark's plain reference
(``bench/models/deepseek-v2-lite-16b.py``), the frozen base both draw from
one seed, the held-expert share of a routed layer, and the paper's cuts on
a 6-unit model."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import adaptive
from repro.core.lm_unit import SplitLoRAUnitModel
from repro.models import moe as E

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")


def _load(rel):
    spec = importlib.util.spec_from_file_location(
        "bench_" + rel.replace("/", "_").replace("-", "_")[:-3],
        os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("models/deepseek-v2-lite-16b.py")
PAPER = _load("harness/reference.py")

SEED = 77
N_EXPERTS = 64


def tiny_arch():
    """The published config at tiny widths: every mechanism (dense lead
    layer, MLA with YaRN, a 64-expert top-6 router with unnormalised gates
    and two shared experts) kept."""
    full = get_config("deepseek-v2-lite-16b")
    return dataclasses.replace(
        full, d_model=64, n_heads=2, d_ff=96, vocab_size=96,
        mla=dataclasses.replace(full.mla, kv_lora_rank=16, qk_nope_dim=8,
                                qk_rope_dim=8, v_head_dim=8),
        moe=dataclasses.replace(full.moe, d_ff_expert=16))


def model(arch, n_layers=3, held=8):
    return SplitLoRAUnitModel(arch, n_layers=n_layers, experts_held=held,
                              vocab_rows=arch.vocab_size, lora_rank=4,
                              lora_alpha=8, base_seed=SEED, seq_len=16)


def ref_cfg(arch, n_layers=3, held=8):
    """The reference's configuration dict for the same shapes."""
    m, e, rs = arch.mla, arch.moe, arch.rope_scaling
    return {
        "hidden_size": arch.d_model, "num_attention_heads": arch.n_heads,
        "kv_lora_rank": m.kv_lora_rank, "qk_nope_head_dim": m.qk_nope_dim,
        "qk_rope_head_dim": m.qk_rope_dim, "v_head_dim": m.v_head_dim,
        "intermediate_size": arch.d_ff,
        "moe_intermediate_size": e.d_ff_expert,
        "n_shared_experts": e.n_shared, "num_experts_per_tok": e.top_k,
        "n_routed_experts": held, "router_experts": e.n_experts,
        "first_k_dense_replace": 1, "num_hidden_layers": n_layers,
        "vocab_size": arch.vocab_size, "rms_norm_eps": 1e-6,
        "rope_theta": arch.rope_theta,
        "rope_scaling": {"factor": rs.factor, "mscale": rs.mscale,
                         "mscale_all_dim": rs.mscale_all_dim,
                         "beta_fast": rs.beta_fast,
                         "beta_slow": rs.beta_slow,
                         "original_max_position_embeddings":
                             rs.original_max_position_embeddings},
        "model_kwargs": {"lora_rank": 4, "lora_alpha": 8, "base_seed": SEED},
        "seq_len": 16, "zipf_exponent": 1.2}


@pytest.fixture(scope="module")
def setup():
    arch = tiny_arch()
    cfg = ref_cfg(arch)
    units, head = REF.init(jax.random.PRNGKey(3), cfg)
    # B from a normal instead of zero, so that A's gradients are not zero
    units = [{t: dict(ad, b=0.5 * jax.random.normal(
        jax.random.PRNGKey(u * 8 + i), ad["b"].shape))
        for i, (t, ad) in enumerate(sorted(unit.items()))}
        for u, unit in enumerate(units)]
    data = REF.make_data(5, 2, 2, 1, cfg)
    return arch, cfg, units, head, data


def test_make_data_is_next_token_over_the_slice(setup):
    _, cfg, _, _, data = setup
    x, y = data["images"], data["labels"]
    assert x.shape == y.shape == (2, 2, 16) and x.dtype == np.int32
    assert (x[..., 0] == REF.BOS).all()
    np.testing.assert_array_equal(x[..., 1:], y[..., :-1])
    assert 0 <= y.min() and y.max() < cfg["vocab_size"]


@pytest.mark.parametrize("cut", [2, 3])
def test_unit_stack_matches_reference(setup, cut):
    """Logits, loss and every adapter's gradient, through a cut after the
    dense layer or after the first MoE layer."""
    arch, cfg, units, head, data = setup
    m = model(arch)
    x, y = jnp.asarray(data["images"][0]), jnp.asarray(data["labels"][0])

    def prog_loss(u):
        feats = m.apply_units(u[cut:], m.apply_units(u[:cut], x, 0), cut)
        return m.head_loss(head, feats, y)

    (loss, rows), g = jax.value_and_grad(prog_loss, has_aux=True)(units)
    ref_loss, ref_g = jax.value_and_grad(REF.loss)(units, head, x, y, cfg)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    lg = m.head_predict(head, m.apply_units(units, x, 0))
    ref_lg = REF.logits(units, head, x, cfg, SEED)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                               rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)
    assert any(float(jnp.abs(a).max()) > 0 for a in jax.tree.leaves(g))
    # 2 MoE layers x 32 tokens x top-6, on 8 of 64 experts
    assert 0 < int(rows) <= 2 * 32 * 6


def test_base_is_bit_identical_to_reference(setup):
    arch, cfg, _, _, _ = setup
    m = model(arch)
    prog = m.make_frozen(SEED)
    ref = REF.base(cfg, SEED)
    eq = np.testing.assert_array_equal
    eq(prog["units"][0]["embed"], ref["embed_tokens"])
    eq(prog["head"]["head"], ref["lm_head"])
    d = arch.d_model
    for u, (p, r) in enumerate(zip(prog["units"][1:], ref["layers"])):
        mx = p["mixer"]
        eq(mx["wq"].reshape(d, -1), r["q_proj"])
        eq(jnp.concatenate([mx["w_dkv"], mx["w_kr"]], 1),
           r["kv_a_proj_with_mqa"])
        eq(jnp.concatenate([mx["w_uk"], mx["w_uv"]], -1).reshape(
            arch.mla.kv_lora_rank, -1), r["kv_b_proj"])
        eq(mx["wo"].reshape(-1, d), r["o_proj"])
        f = p["ffn"]
        if u == 0:
            eq(f["wi_gate"]["w"], r["gate_proj"])
            eq(f["wo"]["w"], r["down_proj"])
            continue
        eq(f["router"], r["gate"])
        for e, ex in enumerate(r["experts"]):
            eq(f["wi_gate"][e], ex["gate_proj"])
            eq(f["wi_up"][e], ex["up_proj"])
            eq(f["wo"][e], ex["down_proj"])
        eq(f["shared"]["wo"]["w"], r["shared_experts"]["down_proj"])


def _moe_layer(arch, held):
    """The base of unit 2 (the first MoE layer) holding its first ``held``
    experts."""
    m = model(arch, n_layers=2, held=held)
    return m.make_frozen(SEED)["units"][2]["ffn"]


def test_held_shares_sum_to_the_uncut_layer():
    """The 8 shares of a 64-expert layer, with the shared experts
    (and attention, which every share computes alike) counted once, add up
    to the uncut reference layer."""
    arch = tiny_arch()
    assert arch.moe.n_experts == N_EXPERTS and not arch.moe.norm_topk_prob
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, arch.d_model))
    full = _moe_layer(arch, N_EXPERTS)
    shares, rows = [], 0
    for s in range(8):
        p = dict(full, **{k: full[k][s * 8:(s + 1) * 8]
                          for k in ("wi_gate", "wi_up", "wo")})
        y, r = E.moe_held(p, arch, x, s * 8)
        shares.append(y)
        rows += int(r)
    shared = E.L.mlp(p["shared"], x, "swiglu")
    total = sum(shares) - 7 * shared
    cfg = ref_cfg(arch, n_layers=2, held=N_EXPERTS)
    uncut = REF._moe(cfg, REF.base(cfg, SEED)["layers"][1], x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=2e-4, atol=2e-5)
    # every token's top-6 lands on some share
    assert rows == 2 * 16 * arch.moe.top_k


def test_no_token_dropped_under_an_adversarial_router():
    """Every token's first choice is held expert 0: a capacity of 1.25 x
    the mean load would drop most of them; the held layer computes all."""
    arch = tiny_arch()
    p = dict(_moe_layer(arch, 8))
    p["router"] = p["router"].at[:, 0].set(0.0).at[0, 0].set(1e3)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, arch.d_model))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)   # all pick expert 0
    y, rows = E.moe_held(p, arch, x, 0)
    cfg = ref_cfg(arch, n_layers=2, held=8)
    rb = REF.base(cfg, SEED)["layers"][1]
    rb["gate"] = p["router"]
    want = REF._moe(cfg, rb, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    _, top = jax.lax.top_k(jax.nn.softmax(x.reshape(-1, arch.d_model)
                                          @ p["router"]), 6)
    assert (top[:, 0] == 0).all()
    assert int(rows) == int(jnp.sum(top < 8))


def test_grouped_matmul_carries_input_gradients_over_frozen_experts():
    """Rows batched over shared experts (the RSU axis) take the custom
    batching rule; the input gradient is ``ragged_dot``'s, row by row, and
    a gradient for the frozen weights is refused, not silently zero."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 12, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 4))
    sizes = jnp.array([[4, 5], [12, 0], [0, 3]], jnp.int32)
    sq = lambda y: jnp.sum(y ** 2)
    got = jax.grad(jax.checkpoint(lambda x: sq(jax.vmap(
        lambda a, s: E.grouped_matmul(a, w, s))(x, sizes))))(x)
    want = [jax.grad(lambda a: sq(jax.lax.ragged_dot(a, w, sizes[i])))(x[i])
            for i in range(3)]
    np.testing.assert_allclose(np.asarray(got), np.stack(want), rtol=1e-6)
    # rows past a batch row's assigned rows get no gradient
    assert not np.asarray(got[0, 9:]).any() and not np.asarray(got[2, 3:]).any()
    with pytest.raises(TypeError, match="frozen"):
        jax.grad(lambda w: sq(E.grouped_matmul(x[0], w, sizes[0])))(w)


def test_paper_cuts_on_six_units_match_reference():
    """The program's traced Eq. 3 rule, clipped to U - 1 = 5 as the fused
    program clips it, against the reference's ``paper_cuts``."""
    rng = np.random.default_rng(0)
    rates = rng.uniform(0, 250e6, 512).astype(np.float32)
    serving = rng.integers(-1, 4, 512).astype(np.int32)
    cuts = np.asarray(adaptive.paper_threshold_traced(jnp.asarray(rates)))
    prog = np.where((serving >= 0) & (cuts > 0), np.clip(cuts, 1, 5), 0)
    ref = PAPER.paper_cuts(serving, rates, 6)
    np.testing.assert_array_equal(prog, ref)
    assert set(np.unique(ref)) == {0, 2, 4, 5}


def test_profile_is_per_sequence():
    arch = tiny_arch()
    m = model(arch)
    prof = m.profile()
    assert prof.n_units == m.n_units == 4
    assert prof.unit_fwd_flops[0] == 16 * arch.d_model
    assert prof.unit_fwd_flops[1] > prof.unit_fwd_flops[2] > 0
    assert prof.unit_param_bytes[0] == 0 and prof.head_param_bytes == 0
