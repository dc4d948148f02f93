"""Tensor-parallel serving mesh — GSPMD sharding for the paged stack.

Reference analog: PaddleNLP `llm/` predict with mp_degree > 1 — the
Megatron-TP serving layout (qkv/gate/up column-split, o/down row-split,
the fused-attention KV cache sharded on its head axis) the reference
builds out of mpu layers (upstream-canonical, unverified — SURVEY.md
§3.5). Training already has this shape: `parallel/sharding.py` owns the
hybrid mesh and `llama.infer_param_specs` IS the serving TP table.

TPU-native design (ROADMAP direction 1): parallelism is not code —
GSPMD (arxiv 2105.04663) partitions the batcher's existing step
programs from sharding annotations on their INPUTS, so the fused,
quantized, speculative and disaggregated serving paths all go
multi-chip through one refactor. `MeshConfig` is the one knob: the
batcher builds a 1-D device mesh over the model axis, `device_put`s
weights and the paged KV pool to their shards at construction, and
AOT-lowers every step shape from sharded avals. The host-side
scheduler (block allocator, slot state, admission) is untouched:
slot/scheduler arrays are replicated, per-call host inputs are
uncommitted and auto-placed by dispatch, and XLA inserts the
collectives (activation all-gathers ahead of the o/down dots).

The one exception to "parallelism is not code" is the Pallas ragged
kernel: GSPMD cannot partition a pallas_call, so under a mesh the
step programs call it `shard_map`-wrapped over the head-sharded pool
(nlp/ragged_attention.py `_shard_specs`) — each device runs the
per-device kernel on its contiguous head shard and the head-axis
concat keeps the result bit-identical to the mesh-off kernel. The
speculative suffix-slab verify rides the same wrapper (the slab and
accept walk shard on heads naturally; slab visibility and the block
table stay replicated), and the verify's activation all-gather is the
same output-split convention below — so mesh x pallas x speculation
compose with greedy output still BIT-identical to the unsharded
batcher.

Unlike the training table (`llama.param_specs`) and the generation
table (`llama.infer_param_specs`), serving NEVER shards a contracted
dim: Megatron's o/down row split would make those matmuls per-shard
partials + a psum whose bf16 summation order differs from the
unsharded dot — ulp logit drift that flips near-tie argmaxes
mid-decode. Serving output-splits o/down instead, so every output
element is one full-contraction dot in the unsharded order and
greedy decode is BIT-identical to the mesh-off batcher (the gate
tests/test_tp_serving.py enforces).

Sharding table (axis `mp`, TP degree t):

    weights   q/k/v/gate/up_proj   [L, Din, Dout]   P(None, None, mp)
              o/down_proj          [L, Din, Dout]   P(None, None, mp)
              '<w>:scale' (w8)     [L, 1,   Dout]   weight spec, the
                                                    contracted dim
                                                    forced replicated
              lm_head              [D, V]           P(None, mp)
              embed / norms                         replicated
    KV pool   k/v                  [L, N, bs, KV, hd]
                                   P(None, None, None, mp, None)
    scales    k/v int8 pool scales [L, N]           replicated (per-
                                   (layer, block) abs-max — no head
                                   axis to shard)
    scheduler table/lengths/slot state              replicated

Divisibility: t must divide num_attention_heads AND
num_key_value_heads (pool head axis; contiguous q-head shards then
align with their kv-head shard under GQA), intermediate_size
(gate/up/down), and vocab_size (lm_head column split).

CPU development recipe: set `XLA_FLAGS=--xla_force_host_platform_
device_count=N` BEFORE jax initializes and a single host exposes N
devices — `tests/test_tp_serving.py` runs the whole TP matrix this
way, no TPU required.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

# Sharded weight names (every projection is output-split — see the
# exactness note in `param_pspecs`); this list only drives the
# per-device byte accounting in `shard_info`.
_SHARDED_LAYER_KEYS = ("q_proj", "k_proj", "v_proj", "o_proj",
                       "gate_proj", "up_proj", "down_proj")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Serving-mesh description: a 1-D tensor-parallel device mesh.

    `tp` is the TP degree (device count), `axis` the mesh axis name
    every PartitionSpec refers to, `devices` an optional explicit
    tuple of `jax.devices()` indices (default: the first `tp`).
    Frozen + hashable: `.key()` rides every compiled-shape memo key
    (the KEY001-enforced convention), so two batchers that differ
    only in mesh layout can never serve each other's executables."""

    tp: int = 1
    axis: str = "mp"
    devices: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if int(self.tp) < 1:
            raise ValueError(f"tp degree must be >= 1, got {self.tp}")
        if not self.axis or not isinstance(self.axis, str):
            raise ValueError(f"axis must be a non-empty str, "
                             f"got {self.axis!r}")
        if self.devices is not None and len(self.devices) != self.tp:
            raise ValueError(
                f"devices names {len(self.devices)} device indices "
                f"but tp={self.tp}")

    def key(self) -> Tuple:
        """The memo-key element: mesh geometry + device assignment.
        Everything that changes the compiled program's partitioning
        is here; nothing else is (a key element never read under
        trace is a spurious-recompile storm — KEY001 kind b)."""
        return ("tp", int(self.tp), self.axis,
                self.devices if self.devices is None
                else tuple(int(d) for d in self.devices))

    def validate_for(self, cfg) -> None:
        """Fail fast on a geometry the sharding table can't split:
        every sharded dim must divide evenly (GSPMD would otherwise
        pad or refuse shapes mid-warmup, far from the misconfig)."""
        t = int(self.tp)
        for what, n in (("num_attention_heads", cfg.num_attention_heads),
                        ("num_key_value_heads", cfg.num_key_value_heads),
                        ("intermediate_size", cfg.intermediate_size),
                        ("vocab_size", cfg.vocab_size)):
            if n % t:
                raise ValueError(
                    f"tp={t} does not divide {what}={n} — every "
                    f"sharded dim must split evenly across the mesh")

    def build(self):
        """Construct the `jax.sharding.Mesh`, validated against the
        visible device set. CPU dev: force N host devices with
        XLA_FLAGS=--xla_force_host_platform_device_count=N before
        jax initializes."""
        import jax
        import numpy as np
        from jax.sharding import Mesh
        devs = jax.devices()
        if self.devices is not None:
            bad = [d for d in self.devices if not 0 <= d < len(devs)]
            if bad:
                raise ValueError(
                    f"device indices {bad} out of range — "
                    f"jax.devices() has {len(devs)} devices")
            picked = [devs[d] for d in self.devices]
        else:
            if len(devs) < self.tp:
                raise ValueError(
                    f"mesh wants tp={self.tp} devices but jax sees "
                    f"{len(devs)} — on CPU set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count="
                    f"{self.tp} before jax initializes")
            picked = devs[:self.tp]
        return Mesh(np.array(picked), (self.axis,))

    def describe(self) -> Dict[str, Any]:
        """Attribution stamp for snapshot()/health()/trace_report:
        mesh shape + the platform it landed on."""
        return {"tp": int(self.tp), "axis": self.axis,
                "devices": (list(range(self.tp))
                            if self.devices is None
                            else [int(d) for d in self.devices])}


def param_pspecs(cfg, params) -> Dict[str, Any]:
    """PartitionSpec tree for a serving param tree on axis 'mp' —
    `llama.infer_param_specs` (no ZeRO axis: weights stay resident so
    decode inserts no per-step param all-gathers) with the serving
    exactness override below, extended over weight-only-quantized
    ':scale' leaves via `generation.quantized_specs`."""
    from jax.sharding import PartitionSpec as P
    from ..nlp import llama
    from ..nlp.generation import quantized_specs
    specs = llama.infer_param_specs(cfg)
    # Serving invariant: greedy output must be BIT-identical to the
    # unsharded batcher. Megatron row-splits o/down on the CONTRACTED
    # dim, which turns each matmul into per-shard partials + a psum
    # whose bf16 summation order differs from the unsharded dot — ulp
    # drift, enough to flip a near-tie argmax mid-decode. Serving
    # output-splits them instead: GSPMD all-gathers the (head/ffn-
    # sharded) activations and every output element is one
    # full-contraction dot in the unsharded order. Trades the psum for
    # an activation all-gather and keeps every weight sharded.
    specs["layers"]["o_proj"] = P(None, None, "mp")
    specs["layers"]["down_proj"] = P(None, None, "mp")
    if any(k.endswith(":scale") for k in params["layers"]):
        specs = quantized_specs(specs, params)
    return specs


def _rename_axis(spec, new: str):
    """Rewrite a PartitionSpec's 'mp' entries to the mesh's axis name
    (identity for the default axis)."""
    from jax.sharding import PartitionSpec as P
    return P(*[new if a == "mp" else a for a in spec])


def build_shardings(mesh_cfg: MeshConfig, cfg, params):
    """(mesh, param sharding tree, pool sharding, replicated sharding)
    — everything the batcher pins at construction and lowers from.
    The KV pool shards on its head axis (dim 3 of [L, N, bs, KV, hd]);
    the int8 scale pools, block table and slot arrays are replicated
    (see the module sharding table)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh_cfg.validate_for(cfg)
    mesh = mesh_cfg.build()
    ax = mesh_cfg.axis
    pspecs = jax.tree_util.tree_map(
        lambda s: _rename_axis(s, ax), param_pspecs(cfg, params),
        is_leaf=lambda x: isinstance(x, P))
    shard_params = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    shard_pool = NamedSharding(mesh, P(None, None, None, ax, None))
    shard_repl = NamedSharding(mesh, P())
    return mesh, shard_params, shard_pool, shard_repl


def shard_info(mesh_cfg: MeshConfig, batcher) -> Dict[str, Any]:
    """The observability stamp: mesh shape plus PER-DEVICE byte
    accounting — the pool's K/V tensors split by tp (head-axis
    shards), the int8 scale pools and scheduler state replicated, so
    per-device bytes = scales + (pool - scales)/tp. trace_report's
    replica column attributes multi-chip replicas from this. The mesh
    dict carries the replica's RESOLVED fast-path backends
    (attention_impl, spec_backend) so a fleet operator can see which
    replicas actually run the kernel/spec paths, not just which were
    asked to."""
    t = int(mesh_cfg.tp)
    total = batcher.kv_pool_bytes()
    scales = 0
    c = batcher.cache
    if c.k_scale is not None:
        scales = int(c.k_scale.nbytes + c.v_scale.nbytes)
    per_dev = scales + (total - scales) // t
    sharded_w = 0
    layers = batcher.params["layers"]
    for name in _SHARDED_LAYER_KEYS:
        sharded_w += int(layers[name].nbytes)
    if "lm_head" in batcher.params:
        sharded_w += int(batcher.params["lm_head"].nbytes)
    w_total = batcher.weight_bytes()
    mesh_d = mesh_cfg.describe()
    mesh_d["attention_impl"] = batcher.attention_impl
    mesh_d["spec_backend"] = (batcher.spec_attention_impl
                              if batcher.speculative else None)
    return {
        "mesh": mesh_d,
        "kv_pool_bytes_per_device": per_dev,
        "weight_bytes_per_device":
            (w_total - sharded_w) + sharded_w // t,
    }
