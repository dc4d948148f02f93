"""chip_smoke.py off the chip: it refuses to run without a TPU, a failed
phase fails the run, and (slow) the whole script rehearses at a tiny
size with the kernels interpreted.

The rehearsal steers the script from HERE — the script has no option
for it: `require_tpu` is stubbed, `Sizes.flagship` returns a tiny
config, "auto" resolves to the Pallas backend, the kernels run in
interpret mode through FLAGS_pallas_interpret, and the compiled-text
marker of a kernel is blanked (an interpreted kernel leaves none).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

FAKE_DEVICE = {"platform": "tpu", "kind": "rehearsal (CPU, interpreted)",
               "count": 4}


def _tiny():
    from paddle_tpu.nlp import llama
    cfg = llama.LlamaConfig.tiny(
        hidden_size=128, intermediate_size=256, num_attention_heads=8,
        num_key_value_heads=4, max_position_embeddings=128)
    return chip_smoke.Sizes(
        cfg=cfg, max_batch=2, block_size=4, max_total_len=64, max_new=4,
        prefill_buckets=(8, 16), prompt_lens=(5, 12, 20, 9), train_batch=4,
        train_seq=128, norm_rows=64)


@pytest.fixture
def steered(monkeypatch):
    """The test-only hooks of the rehearsal."""
    from paddle_tpu.core import flags
    from paddle_tpu.nlp import paged
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda chips: dict(FAKE_DEVICE))
    monkeypatch.setattr(chip_smoke.Sizes, "flagship",
                        staticmethod(_tiny))
    monkeypatch.setattr(chip_smoke, "_hbm", lambda device=None: {
        "bytes_in_use": 1, "peak_bytes_in_use": 1, "bytes_limit": 1})
    # an interpreted kernel leaves no custom call in the program text
    monkeypatch.setattr(chip_smoke, "KERNEL_MARKER", "")
    resolve = paged.resolve_attention_impl
    monkeypatch.setattr(
        paged, "resolve_attention_impl",
        lambda impl: "pallas" if impl == "auto" else resolve(impl))
    # the persistent cache is the chip's business, not the test tree's
    monkeypatch.setattr(
        "paddle_tpu.core.compile_cache.enable_compile_cache",
        lambda: "off (rehearsal)")
    flags.set_flags({"FLAGS_pallas_interpret": True})
    yield
    flags.set_flags({"FLAGS_pallas_interpret": False})


def _last_json(out: str):
    return json.loads(out.strip().splitlines()[-1])


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_failed_kernel_fails_the_run(steered, monkeypatch, capsys):
    from paddle_tpu.nlp import ragged_attention

    def broken(*a, **k):
        raise RuntimeError("kernel made to fail")

    monkeypatch.setattr(ragged_attention, "ragged_paged_attention", broken)
    for later in ("phase_serve", "phase_train"):
        monkeypatch.setattr(chip_smoke, later, lambda sizes, seed: None)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert "kernel made to fail" in out and "[kernels] FAILED" in out
    assert _last_json(out) == {"ok": False, "device": FAKE_DEVICE}


def test_near_tie_check_refuses_a_real_divergence(steered):
    """compare_tokens recomputes the logits where two streams differ; a
    token far from the maximum is not a near-tie."""
    import jax
    import numpy as np
    from paddle_tpu.nlp import llama
    sizes = _tiny()
    params = llama.init_params(jax.random.key(0), sizes.cfg)
    prompt = list(range(1, 8))
    logits = chip_smoke._next_logits_fn(sizes.cfg, sizes, "xla")(
        params, prompt)
    best, worst = int(np.argmax(logits)), int(np.argmin(logits))
    same = chip_smoke.compare_tokens(params, sizes, [prompt], [[best, 3]],
                                     [[best, 3]])
    assert "identical" in same[0]
    with pytest.raises(AssertionError, match="not a near-tie"):
        chip_smoke.compare_tokens(params, sizes, [prompt], [[best]],
                                  [[worst]])


@pytest.mark.slow
@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_cpu_rehearsal(steered, capsys, argv):
    """Every phase end to end at a tiny size, kernels interpreted, the
    four-chip phases on forced host devices (conftest gives eight)."""
    rc = chip_smoke.main(argv)
    out = capsys.readouterr().out
    assert rc == 0, out[-4000:]
    assert _last_json(out) == {"ok": True, "device": FAKE_DEVICE}
