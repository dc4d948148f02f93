"""The MLA + expert decoder under jax.grad (nlp/mla_train.py): the mHC
residual path (nlp/hyper.py), the bias-corrected sigmoid router, the
differentiable form of the served expert share (moe.expert_share_train)
and the multi-token-prediction loss, at a tiny size on the CPU, against the benchmark's plain reference
(benchmark/reference/mhc_mla_moe_decoder.py), which imports nothing of
paddle_tpu."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import mhc_mla_moe_decoder as family
from benchmark.reference import mhc_mla_moe_decoder as ref
from paddle_tpu.nlp import hyper, llama, mla, mla_train, moe, train

F32 = jnp.float32
_sub = mla_train._sub
# every published key of the family at a size a CPU test can hold: four
# chips' shares of 16 routed experts, top-4, 4 streams, one module
CONFIG = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
    "kv_lora_rank": 32, "max_position_embeddings": 256,
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 2, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 48, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 256,
    "trained_dtype": "float32",
    "share": {"router_experts": 16, "experts_first": 4},
}
B, S = 2, 24


@pytest.fixture(scope="module")
def model():
    d = family.dims(CONFIG)
    cfg = family.program_config(CONFIG)
    params = family.make_params(7, d, F32)
    tokens = family.train_tokens(family.seed_key(7), 0, B, S, d["V"])
    return d, cfg, params, tokens


def _flat(tree):
    return {jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


# ---------------------------------------------------------------------------
# (a) the program against the plain reference: each loss and every leaf's
# gradient of it, apart
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def both_grads(model):
    d, cfg, params, tokens = model

    def two(losses_of):
        """(main, module) losses and the gradient of each: one forward,
        two backward passes, one program."""
        (main, side), vjp = jax.vjp(losses_of, params)
        one, zero = jnp.ones((), F32), jnp.zeros((), F32)
        return (main, vjp((one, zero))[0]), (side, vjp((zero, one))[0])

    def program(p):
        m = mla_train.loss_and_metrics(p, tokens, cfg)[1]
        return m["loss_main"], m["loss_mtp"]

    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda: two(program))()
        want = jax.jit(lambda: two(lambda p: ref.losses(p, tokens, d)))()
    return {"main": (got[0], want[0]), "mtp": (got[1], want[1])}


@pytest.mark.parametrize("which", ["main", "mtp"])
def test_loss_and_every_leafs_gradient_match_the_reference(both_grads,
                                                           which):
    (loss, grads), (loss_ref, grads_ref) = both_grads[which]
    assert abs(float(loss) - float(loss_ref)) < 2e-5
    got, want = _flat(grads), _flat(grads_ref)
    assert set(got) == set(want)
    top = max(float(jnp.linalg.norm(v)) for v in want.values())
    for name, w in want.items():
        err = float(jnp.linalg.norm(got[name] - w))
        assert err <= 2e-4 * max(float(jnp.linalg.norm(w)), 1e-3 * top), \
            (name, err, float(jnp.linalg.norm(w)))
    if which == "main":
        # the module's leaves are no part of the main loss
        assert all(float(jnp.max(jnp.abs(v))) == 0.0
                   for k, v in got.items() if "mtp_" in k)
    for k, v in got.items():
        if k.endswith("e_bias']"):
            assert float(jnp.max(jnp.abs(v))) == 0.0, k


# ---------------------------------------------------------------------------
# (b) the residual path
# ---------------------------------------------------------------------------

def _coef(X, hp, iters=20):
    return hyper.coefficients(X, hp, n=4, iters=iters, eps=1e-6,
                              clamp=(-30.0, 30.0), norm_eps=1e-6)


@pytest.mark.parametrize("iters,tol", [(20, 1e-5), (1, None)])
def test_h_res_is_doubly_stochastic_after_the_rounds(iters, tol):
    k = jax.random.split(jax.random.key(3), 3)
    hp = hyper.init_sublayer(k[0], 4, 16, F32)
    hp["b"] = jax.random.normal(k[1], (24,)) * 1.0
    hp["a"] = jnp.full((3,), 0.5)
    X = jax.random.normal(k[2], (50, 64))
    _, _, h_res = _coef(X, hp, iters)
    rows = float(jnp.max(jnp.abs(jnp.sum(h_res, -1) - 1.0)))
    cols = float(jnp.max(jnp.abs(jnp.sum(h_res, -2) - 1.0)))
    assert rows < 1e-5                  # the rows come last
    if tol is not None:
        assert cols < tol
    else:
        assert cols > 1e-2              # one round is not enough: a
        #                                 control that cuts them must show
    assert float(jnp.min(h_res)) > 0.0


def test_dot_f32_of_a_bfloat16_state_is_float32_exact():
    """The coefficients' GEMM takes the bfloat16 streams as they are (no
    float32 copy of the state) and still computes in float32: three native
    GEMMs against the weight's three bfloat16 pieces."""
    k = jax.random.split(jax.random.key(9), 3)
    x = jax.random.normal(k[0], (96, 256)).astype(jnp.bfloat16)
    w = jax.random.normal(k[1], (256, 24)) * 0.05
    ct = jax.random.normal(k[2], (96, 24))
    hi = jax.lax.Precision.HIGHEST
    want = jnp.dot(x.astype(F32), w, precision=hi)
    got = hyper.dot_f32(x, w)
    assert got.dtype == F32
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * float(
        jnp.max(jnp.abs(want))))
    # a plain bfloat16 GEMM is a hundred times further off
    assert float(jnp.max(jnp.abs(jnp.dot(x, w.astype(jnp.bfloat16)).astype(
        F32) - want))) > 1e-4 * float(jnp.max(jnp.abs(want)))
    dx, dw = jax.grad(lambda x, w: jnp.sum(hyper.dot_f32(x, w) * ct),
                      (0, 1))(x, w)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == F32
    np.testing.assert_allclose(dw, jnp.dot(x.astype(F32).T, ct, precision=hi),
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(
        dx.astype(F32), jnp.dot(ct, w.T, precision=hi), rtol=0,
        atol=2 ** -7 * float(jnp.max(jnp.abs(ct @ w.T))))  # dx's own rounding
    # any other state takes the plain GEMM at the highest precision
    xf = x.astype(F32) + 1e-3
    np.testing.assert_allclose(hyper.dot_f32(xf, w),
                               jnp.dot(xf, w, precision=hi), rtol=1e-5,
                               atol=1e-6)


def test_sublayer_with_identity_mix_is_the_plain_residual():
    """a = 0 and the biases that give H_res = I and fixed H_pre, H_post:
    n copies of the plain pre-norm residual, x + F(x) a stream."""
    n, D, T = 4, 16, 10
    k = jax.random.split(jax.random.key(5), 3)
    hp = hyper.init_sublayer(k[0], n, D, F32)
    big = 40.0      # exp(clip(+-40)) -> exp(30) on the diagonal, exp(-30) off
    b_res = jnp.where(jnp.eye(n, dtype=bool), big, -big).reshape(-1)
    # H_pre = sigmoid(b) = 1/n each, H_post = 2 sigmoid(0) = 1
    b_pre = jnp.full((n,), float(np.log((1 / n) / (1 - 1 / n))))
    hp = {**hp, "a": jnp.zeros((3,)),
          "b": jnp.concatenate([b_pre, jnp.zeros((n,)), b_res])}
    x = jax.random.normal(k[1], (T, D))
    w = jax.random.normal(k[2], (D, D)) * 0.3
    fn = lambda h: jnp.tanh(h @ w)                          # noqa: E731
    out = hyper.sublayer(hyper.enter(x, n), hp, fn, n=n, iters=20, eps=1e-6,
                         clamp=(-30.0, 30.0), norm_eps=1e-6)
    want = x + fn(x)                    # H_pre X = the mean of n copies
    for j in range(n):
        np.testing.assert_allclose(out[:, j * D:(j + 1) * D], want,
                                   rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(hyper.leave(out, n), n * want, rtol=2e-5,
                               atol=2e-5)


def test_hc_mult_one_is_the_plain_decoder():
    cfg = mla_train.MlaTrainConfig.tiny(hc_mult=1, num_hidden_layers=2,
                                        num_nextn_predict_layers=0)
    params = mla_train.init_params(jax.random.key(0), cfg)
    assert not any("hc_" in k or "mtp_" in k for k in _flat(params))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 256)
    loss, m = jax.jit(lambda p: mla_train.loss_and_metrics(p, tokens, cfg))(
        params)
    assert float(m["loss_mtp"]) == 0.0
    assert np.isfinite(float(loss)) and int(m["moe_pairs"]) > 0


# ---------------------------------------------------------------------------
# (c) the share ties to the model: four chips' shares add up to the uncut
# layer, output and input gradient
# ---------------------------------------------------------------------------

def test_four_shares_add_up_to_the_uncut_layer():
    T, D, F, E, k = 48, 32, 16, 16, 4
    ks = jax.random.split(jax.random.key(11), 9)
    nrm = lambda key, shape, s=0.3: jax.random.normal(key, shape) * s  # noqa: E731
    full = {"router": nrm(ks[0], (D, E)), "e_bias": nrm(ks[1], (E,), 0.1),
            "experts_gate": nrm(ks[2], (E, D, F)),
            "experts_up": nrm(ks[3], (E, D, F)),
            "experts_down": nrm(ks[4], (E, F, D)),
            "gate_proj": nrm(ks[5], (D, F)), "up_proj": nrm(ks[6], (D, F)),
            "down_proj": nrm(ks[7], (F, D))}
    h = jax.random.normal(ks[8], (T, D))
    d = {"k": k, "norm_topk": True, "route_scale": 2.0, "first": 0, "n": E}
    mm = lambda a, b: a @ b                                 # noqa: E731

    def uncut(h):
        """The whole layer by the reference: all 16 experts + shared."""
        idx, gates = ref.route(h, full, d)
        y = ref._mlp(h, full["gate_proj"], full["up_proj"],
                     full["down_proj"], mm)
        for j in range(E):
            g = jnp.sum(jnp.where(idx == j, gates, 0.0), -1)
            y = y + g[:, None] * ref._mlp(
                h, full["experts_gate"][j], full["experts_up"][j],
                full["experts_down"][j], mm)
        return y

    def shares(h):
        """Four chips' parts by the program, the shared expert once."""
        y = mla_train._mlp(h, full, F32)
        pairs = 0
        for c in range(4):
            lp = {"router": full["router"], "e_bias": full["e_bias"],
                  **{m: full[m][4 * c:4 * c + 4] for m in (
                      "experts_gate", "experts_up", "experts_down")}}
            part, st = moe.expert_share_train(h, lp, k=k, first=4 * c,
                                              scale=2.0,
                                              score="sigmoid_bias")
            y, pairs = y + part, pairs + st["moe_pairs"]
        return y, pairs

    with jax.default_matmul_precision("highest"):
        ct = jnp.cos(jnp.arange(T * D, dtype=F32)).reshape(T, D)
        (y, pairs), g = jax.jit(lambda h: (shares(h), jax.grad(
            lambda h: jnp.sum(shares(h)[0] * ct))(h)))(h)
        y_ref, g_ref = jax.jit(lambda h: (uncut(h), jax.grad(
            lambda h: jnp.sum(uncut(h) * ct))(h)))(h)
        assert int(pairs) == T * k              # every pair on one chip
        np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(g, g_ref, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# (d) the selection bias
# ---------------------------------------------------------------------------

def test_bias_moves_the_selection_not_the_gates_and_gets_no_gradient():
    T, D, E, k = 64, 16, 16, 4
    ks = jax.random.split(jax.random.key(2), 3)
    h = jax.random.normal(ks[0], (T, D))
    w = jax.random.normal(ks[1], (D, E)) * 0.5
    bias = jax.random.normal(ks[2], (E,)) * 0.3
    idx0, g0 = moe.sigmoid_top_k(h, w, k, 2.0)
    idx1, g1 = moe.sigmoid_bias_top_k(h, w, k, 2.0, True, bias)
    idz, gz = moe.sigmoid_bias_top_k(h, w, k, 2.0, True, jnp.zeros((E,)))
    np.testing.assert_array_equal(idz, idx0)        # no bias: the plain one
    np.testing.assert_allclose(gz, g0, rtol=1e-6)
    assert float(np.mean(np.sort(idx1, -1) != np.sort(idx0, -1))) > 0.05
    # the gates are the SCORES at the chosen, normalised and scaled: the
    # bias is not in them
    s = jax.nn.sigmoid(jnp.dot(h, w, precision="highest"))
    top = jnp.take_along_axis(s, idx1, -1)
    np.testing.assert_allclose(g1, 2.0 * top / top.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(g1.sum(-1), 2.0, rtol=1e-5)
    db, dw = jax.grad(lambda b, w: jnp.sum(jnp.sin(
        moe.sigmoid_bias_top_k(h, w, k, 2.0, True, b)[1]
        * jnp.arange(k))), (0, 1))(bias, w)
    assert float(jnp.max(jnp.abs(db))) == 0.0
    assert float(jnp.max(jnp.abs(dw))) > 0.0


# ---------------------------------------------------------------------------
# (e) expert_share_ffn: the served shapes unchanged bit for bit, the
# differentiable form's gradient against a dense per-expert loop
# ---------------------------------------------------------------------------

def _share_case(T, D=32, F=16, E=16, n=4, skew=False, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    lp = {"router": jax.random.normal(ks[0], (D, E)) * 0.5,
          "experts_gate": jax.random.normal(ks[1], (n, D, F)) * 0.2,
          "experts_up": jax.random.normal(ks[2], (n, D, F)) * 0.2,
          "experts_down": jax.random.normal(ks[3], (n, F, D)) * 0.2,
          "e_bias": jax.random.normal(ks[4], (E,)) * 0.1}
    if skew:                # every token picks the held experts first
        lp["e_bias"] = lp["e_bias"].at[3:3 + n].add(5.0)
    return jax.random.normal(ks[5], (T, D)), lp


def _dense_loop(h, lp, k, first, scale, score):
    idx, g = moe._route(h, lp, k, scale, True, score)
    y = jnp.zeros_like(h)
    for j in range(lp["experts_gate"].shape[0]):
        gj = jnp.sum(jnp.where(idx == first + j, g, 0.0), -1)
        y = y + gj[:, None] * (
            (jax.nn.silu(h @ lp["experts_gate"][j])
             * (h @ lp["experts_up"][j])) @ lp["experts_down"][j])
    return y


@pytest.mark.parametrize("T,skew", [(40, False), (256, False), (256, True)])
def test_share_layer_gradient_matches_a_dense_loop(T, skew):
    h, lp = _share_case(T, n=2, skew=skew)
    kw = dict(k=4, first=3, scale=2.0, score="sigmoid_bias")
    with jax.default_matmul_precision("highest"):
        (y, st), got = jax.jit(lambda h, lp: (
            moe.expert_share_train(h, lp, **kw), jax.grad(
                lambda h, lp: jnp.sum(jnp.sin(
                    moe.expert_share_train(h, lp, **kw)[0])), (0, 1))(h, lp))
        )(h, lp)
        y_ref, want = jax.jit(lambda h, lp: (
            _dense_loop(h, lp, 4, 3, 2.0, "sigmoid_bias"), jax.grad(
                lambda h, lp: jnp.sum(jnp.sin(_dense_loop(
                    h, lp, 4, 3, 2.0, "sigmoid_bias"))), (0, 1))(h, lp))
        )(h, lp)
        assert int(st["moe_full_passes"]) == 0      # one buffer holds all
        if skew:
            assert int(st["moe_pairs"]) == 2 * T    # both held, every token
        np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-6)
    for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                         jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(p))


@pytest.mark.parametrize("T,block", [(24, None), (64, None), (70, 16)])
def test_served_form_is_unchanged_bit_for_bit(T, block):
    """The stacked form (what the server hands over) against the code it
    had before this form existed, copied here: the same value and stats,
    bit for bit, and the same buffer rule."""
    h, lp1 = _share_case(T, n=4, seed=4)
    stack = {m: jnp.stack([lp1[m] * 0.5, lp1[m]]) for m in (
        "experts_gate", "experts_up", "experts_down")}
    lp = {"router": lp1["router"], **stack}
    valid = jnp.arange(T) % 7 != 0
    kw = dict(k=4, first=3, scale=2.5, valid=valid, layer=1)
    if block:
        kw["token_block"] = block
    y, st = jax.jit(lambda h: moe.expert_share_ffn(h, lp, **kw))(h)
    # one layer's experts, the differentiable form: the same algorithm
    y2, st2 = jax.jit(lambda h: moe.expert_share_train(
        h, {"router": lp1["router"], **{m: lp1[m] for m in stack}},
        k=4, first=3, scale=2.5, valid=valid))(h)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    for name in ("moe_pairs", "moe_experts_hit", "moe_load_max"):
        assert int(st[name]) == int(st2[name]), name
    assert moe._short_rows(64 * 8, 12, 192) == 128      # PR 26's rule holds
    assert moe._short_rows(576 * 8, 12, 192) == 640


# ---------------------------------------------------------------------------
# (f) the model module follows from the configuration object
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make,module", [
    (llama.LlamaConfig.tiny, llama), (moe.MoeConfig.tiny, moe),
    (lambda: mla_train.MlaTrainConfig.tiny(num_hidden_layers=2), mla_train)])
def test_train_picks_the_module_from_the_configuration(make, module):
    cfg = make()
    assert train.model_of(cfg) is module
    if module is moe:
        return          # its step needs a mesh of its own; the pick is it
    tx = train.make_optimizer(1e-3)
    want = jax.eval_shape(lambda: module.init_params(jax.random.key(0), cfg))
    state = jax.eval_shape(
        lambda: train.init_state(jax.random.key(0), cfg, tx))
    assert jax.tree.structure(state.params) == jax.tree.structure(want)
    # the step traced (not compiled: the metrics test runs one): the
    # module's loss is the one inside
    step = train.make_train_step(cfg, tx, donate=False)
    _, m = jax.eval_shape(step, state,
                          jax.ShapeDtypeStruct((2, 16), jnp.int32))
    assert ("loss_mtp" in m) == (module is mla_train)
    assert {"loss", "grad_norm", "step"} <= set(m)


def test_one_dimensional_leaves_train_in_float32():
    """A bfloat16 scale near 1 has its neighbours 0.0078 away and never
    moves by a 1e-4 step: norm scales, the mHC gains and biases and the
    selection bias are float32 whatever `param_dtype`; the matrices keep
    it."""
    cfg = mla_train.MlaTrainConfig.tiny(
        num_hidden_layers=2, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: mla_train.init_params(jax.random.key(0), cfg))
    flat = {k: v for k, v in params.items() if k != "layers"}
    for group, nd in ((params["layers"], 2), (_sub(flat, "dense_"), 2),
                      (_sub(flat, "mtp_"), 1),
                      ({k: flat[k] for k in ("embed_tokens", "norm",
                                             "lm_head")}, 1)):
        assert group
        for name, v in group.items():
            assert v.dtype == (F32 if v.ndim == nd else jnp.bfloat16), name


# ---------------------------------------------------------------------------
# (g) the step's metrics against a Python count
# ---------------------------------------------------------------------------

def test_step_metrics_agree_with_a_python_count(model):
    d, cfg, params, tokens = model
    tx = train.make_optimizer(1e-4, state_quant="8bit")
    state = train.TrainState(jnp.zeros((), jnp.int32), params,
                             tx.init(params))
    step = train.make_train_step(cfg, tx, donate=False)
    counts = []
    real = ref.route

    def counting(h, w, dd, *a, **k):
        idx, gates = real(h, w, dd, *a, **k)
        jax.debug.callback(lambda i: counts.append(np.asarray(i)), idx)
        return idx, gates

    ref.route = counting
    try:
        with jax.default_matmul_precision("highest"):
            _, m = step(state, tokens)
            main, side = jax.block_until_ready(jax.jit(
                lambda p: ref.losses(p, tokens, d))(params))
    finally:
        ref.route = real
    np.testing.assert_allclose(float(m["loss_main"]), float(main), rtol=1e-5)
    np.testing.assert_allclose(float(m["loss_mtp"]), float(side), rtol=1e-5)
    np.testing.assert_allclose(float(m["loss"]),
                               float(main) + 0.3 * float(side), rtol=1e-5)
    # the pairs routed to experts 4..7 in the reference's forward, over
    # the expert layer and the module's
    assert len(counts) == d["L"] + 1
    held = [np.asarray((i >= d["first"]) & (i < d["first"] + d["n"]))
            for i in counts]
    assert int(m["moe_pairs"]) == sum(int(x.sum()) for x in held)
    loads = [np.bincount(np.asarray(i)[x] - d["first"], minlength=d["n"])
             for i, x in zip(counts, held)]
    assert int(m["moe_load_max"]) == max(int(x.max()) for x in loads)
    assert int(m["moe_experts_hit"]) == sum(int((x > 0).sum())
                                            for x in loads)


@pytest.mark.parametrize("fused", [False, True])
def test_small_leaves_keep_float32_moments(fused):
    """A gain of three scalars shares one block scale in the 8-bit state:
    a float8 code's 6% step would land whole on what the optimizer
    applies. Leaves under 4096 elements keep float32 moments; the rest
    stay 8-bit, in the chunked update and in the fused apply alike."""
    from paddle_tpu.core import flags
    from paddle_tpu.optimizer import quant_state as qs
    k = jax.random.split(jax.random.key(4), 4)
    params = {"a": jnp.asarray([0.1, 0.12, 0.09], jnp.bfloat16),
              "w": (jax.random.normal(k[0], (64, 128)) * 0.02
                    ).astype(jnp.bfloat16)}
    grads = {"a": jnp.asarray([3e-4, -7e-3, 1.1e-5], jnp.bfloat16),
             "w": (jax.random.normal(k[1], (64, 128)) * 1e-3
                   ).astype(jnp.bfloat16)}
    tx = qs.adamw_q_fused(1e-2, b1=0.9, b2=0.95, weight_decay=0.1,
                          clip_norm=None)
    state = tx.init(params)
    assert state.m["a"].dtype == F32 and state.m["a"].shape == (3,)
    assert isinstance(state.m["w"], qs._QTensor)        # 8192 elements
    if fused:
        flags.set_flags({"FLAGS_pallas_interpret": True})
        try:
            new, state = tx.apply_fused(grads, state, params)
        finally:
            flags.set_flags({"FLAGS_pallas_interpret": False})
    else:
        upd, state = tx.update(grads, state, params)
        new = jax.tree.map(lambda p, u: p + u, params, upd)
    g = grads["a"].astype(F32)
    np.testing.assert_allclose(state.m["a"], 0.1 * g, rtol=1e-6)
    np.testing.assert_allclose(state.v["a"], 0.05 * g * g, rtol=1e-6)
    # the first step moves every element by lr (and the weight decay)
    want = params["a"].astype(F32) * (1 - 1e-3) - 1e-2 * jnp.sign(g)
    np.testing.assert_allclose(new["a"].astype(F32), want, rtol=0.01)
    m_w = qs._dequantize(state.m["w"], (64, 128), False)
    np.testing.assert_allclose(m_w, 0.1 * grads["w"].astype(F32),
                               rtol=0.07, atol=1e-7)


# ---------------------------------------------------------------------------
# (h) the served decoder runs one stream
# ---------------------------------------------------------------------------

def test_served_decoder_refuses_more_than_one_stream():
    from paddle_tpu.nlp import paged
    cfg = mla.MlaMoeConfig.tiny(hc_mult=4)
    params = mla.init_params(jax.random.key(0), mla.MlaMoeConfig.tiny())
    with pytest.raises(NotImplementedError, match="hc_mult=4"):
        paged.ContinuousBatcher(params, cfg, max_batch=2, block_size=8,
                                max_total_len=64, max_new_tokens=8)
    with pytest.raises(ValueError, match="group-limited"):
        mla_train.MlaTrainConfig.tiny(topk_method="group_limited_greedy")
    with pytest.raises(NotImplementedError, match="experts over a mesh"):
        mla_train.param_specs(mla_train.MlaTrainConfig.tiny())
