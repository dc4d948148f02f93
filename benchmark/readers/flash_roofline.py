"""Share of its roofline that flash attention reaches in training, in %:
the least seconds one layer's forward and backward need on this chip (the
family's ops-and-bytes count: the causal half once forward, once backward;
the forward that remat runs again is not needed by the algorithm and not
credited), times the backward calls in the trace (one a layer a step), over
the device seconds of every flash event, forward, recomputed and backward."""
from ..harness import device, manifest, xplane


def read(spec, obs):
    if obs.get("trace") is None or "batch" not in obs:
        return None
    secs, _ = xplane.kernel_seconds(obs["trace"], "|".join(spec["patterns"]))
    _, steps = xplane.kernel_seconds(obs["trace"],
                                     "|".join(spec["backward_patterns"]))
    if not secs or not steps:
        return None
    fam = manifest.plugin("models", spec.get("family", "dense_decoder"))
    peak = device.peaks(obs["device_kind"])
    least = sum(fam.roofline_seconds(fam.causal_attention_cost(
        obs["dims"], obs["batch"], obs["seq_len"], backward=back), peak)[0]
        for back in (False, True))
    return 100.0 * least * steps / secs
