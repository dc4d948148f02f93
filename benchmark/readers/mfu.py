"""Model FLOP/s utilization, in %: trained tokens per second times the
FLOPs a token needs (the family's count: forward and backward, causal half
of attention, no recomputation), over chips times the published bf16 peak
of the device_kind."""
from ..harness import device, manifest


def read(spec, obs):
    tok_s = obs["values"].get("train_tok_s")
    if tok_s is None:
        return None
    fam = manifest.plugin("models", spec.get("family", "dense_decoder"))
    peak = device.peaks(obs["device_kind"])["bf16_flops"]
    flops = fam.train_flops_per_token(obs["dims"], obs["seq_len"])
    return 100.0 * tok_s * flops / (obs["chips"] * peak)
