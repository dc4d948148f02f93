"""The plain reference against the program's paged path at a tiny size, and
the weights the two are given."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import dense_decoder as fam
from benchmark.reference import dense_decoder as ref
from benchmark.tests import tiny

# the program in float32 sits within float32 rounding of the reference;
# an int8 KV cache does not (stated tolerance: 1e-3 of the logits' range)
TOL = 1e-3


def _config(dtype):
    return {"model": {**tiny.MODEL, "torch_dtype": dtype}}


def test_a_layer_drawn_alone_equals_its_slice_of_the_stack():
    d = fam.dims(_config("bfloat16"))
    params = fam.make_params(2**31 + 7, d)
    key = fam.seed_key(2**31 + 7)
    for i in range(d["L"]):
        one = fam.layer_weights(fam.layer_key(key, jnp.int32(i)), d,
                                jnp.bfloat16)
        for name, w in one.items():
            np.testing.assert_array_equal(
                np.asarray(w, np.float32),
                np.asarray(params["layers"][name][i], np.float32), name)
    assert params["embed_tokens"].dtype == jnp.bfloat16
    # norm scales are not all ones, so that a dropped scale shows
    assert float(jnp.std(params["norm"].astype(jnp.float32))) > 0.05
    other = fam.make_params(8, d)
    assert not np.array_equal(np.asarray(other["lm_head"], np.float32),
                              np.asarray(params["lm_head"], np.float32))


def _paged_logits(config, seed, tokens, kv_dtype=None):
    """Prefill then decode through the program's paged cache: the first
    half of each row as one prefill call, the rest token by token."""
    from paddle_tpu.nlp import paged
    cfg = fam.program_config(config)
    d = fam.dims(config)
    params = fam.make_params(seed, d, cfg.param_dtype)
    B, T = tokens.shape
    bs, M = 8, -(-T // 8)
    kp, vp, ks, vs = paged.init_pool(cfg, B * M + 1, bs, kv_dtype=kv_dtype)
    table = jnp.arange(1, B * M + 1, dtype=jnp.int32).reshape(B, M)
    cache = paged.PagedKVCache(kp, vp, table, jnp.zeros((B,), jnp.int32),
                               ks, vs)
    half = T // 2
    pos = jnp.broadcast_to(jnp.arange(half, dtype=jnp.int32), (B, half))
    out, cache = paged.forward_paged(
        params, tokens[:, :half], cache, pos, jnp.ones((B, half), bool), cfg,
        is_prefill=False, attention_impl="xla")
    rows = [out]
    for t in range(half, T):
        step, cache = paged.forward_paged(
            params, tokens[:, t:t + 1], cache,
            jnp.full((B, 1), t, jnp.int32), jnp.ones((B, 1), bool), cfg,
            is_prefill=False, attention_impl="xla")
        rows.append(step)
    return np.asarray(jnp.concatenate(rows, axis=1), np.float32)


@pytest.mark.parametrize("kv_dtype,agrees", [(None, True), ("int8", False)])
def test_paged_path_against_the_reference(kv_dtype, agrees):
    config = _config("float32")
    d = fam.dims(config)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, d["V"], (2, 40)), jnp.int32)
    want = np.asarray(ref.logits(5, d, tokens, jnp.float32))
    got = _paged_logits(config, 5, tokens, kv_dtype)
    err = float(np.max(np.abs(got - want))) / float(np.ptp(want))
    assert (err <= TOL) == agrees, err


def test_served_gaps_reads_every_served_token():
    d = fam.dims(_config("bfloat16"))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, d["V"], n).tolist() for n in (9, 17)]
    full = np.zeros((2, 32), np.int32)
    for b, p in enumerate(prompts):
        full[b, :len(p)] = p
    # serve the reference's own greedy tokens: every gap is zero
    served = [[], []]
    for _ in range(5):
        lg = np.asarray(ref.logits(3, d, jnp.asarray(full)))
        for b, p in enumerate(prompts):
            n = len(p) + len(served[b])
            tok = int(lg[b, n - 1].argmax())
            served[b].append(tok)
            full[b, n] = tok
    gaps = ref.served_gaps(3, d, prompts, served, pad=32)
    assert gaps.shape == (10,) and float(gaps.max()) == 0.0
    # one altered token shows as a gap of the size of the logits' spread
    served[1][2] = (served[1][2] + 1) % d["V"]
    bad = ref.served_gaps(3, d, prompts, served, pad=32)
    assert float(bad.max()) > 0.05
    # the control's reading: the token an int8 cache would put first
    low = ref.served_gaps(3, d, prompts, served, pad=32,
                          lower=ref.int8_blocks)
    assert low.shape == (10,) and float(low.min()) >= 0.0
