"""Device mesh construction + named shardings for the engine.

The TPU-native replacement for the reference's engine-delegated TP/PP/EP
flags (SURVEY.md §2.5): a `jax.sharding.Mesh` with axes

    dp — data parallel (replica) axis
    tp — tensor parallel axis (attention heads / MLP hidden / vocab)
    ep — expert parallel axis for MoE (aliases tp by default)

Params and KV cache carry NamedShardings; jit'd steps run under GSPMD and
XLA inserts all-reduces over ICI (scaling-book recipe). No manual
collectives on the inference path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# --------------------------------------------------------------------- #
# Mesh-axis registry — the single source of truth for axis names.
#
# Every collective (`psum`/`ppermute`/`all_gather`/`axis_index`), every
# `PartitionSpec`, and every `mesh.shape[...]` lookup in the package must
# reference one of these names; the `shard-axis-registry` dynolint rule
# (dynamo_tpu/analysis/shard/) resolves axis arguments through call chains
# and fails CI on anything not registered here. Modules import the
# constants instead of repeating the string literals, so a typo is an
# ImportError rather than a silent wrong-axis collective.
# --------------------------------------------------------------------- #

DP_AXIS = "dp"
PP_AXIS = "pp"
SP_AXIS = "sp"
EP_AXIS = "ep"
TP_AXIS = "tp"

#: axis name -> role. Parsed (as AST, never imported) by the shard
#: analysis pack; keep values one-line human-readable.
KNOWN_AXES = {
    DP_AXIS: "data-parallel replica axis",
    PP_AXIS: "pipeline-stage axis (layers sharded across stages)",
    SP_AXIS: "sequence-parallel (ring-attention) axis",
    EP_AXIS: "expert-parallel axis for MoE dispatch",
    TP_AXIS: "tensor-parallel axis (heads / MLP hidden / vocab)",
}

#: outer→inner device-grid order; tp innermost so its all-reduces ride
#: the fastest ICI dimension (scaling-book layout recipe)
MESH_AXIS_ORDER = (DP_AXIS, PP_AXIS, SP_AXIS, EP_AXIS, TP_AXIS)


@dataclass(frozen=True)
class ParallelConfig:
    """Mesh axis sizes. Axis order (outer→inner) is dp, pp, sp, ep, tp —
    tp innermost so its all-reduces ride the fastest ICI dimension
    (scaling-book layout recipe)."""

    tp_size: int = 1
    dp_size: int = 1
    pp_size: int = 1  # pipeline stages
    sp_size: int = 1  # sequence (ring-attention) axis
    ep_size: int = 1  # expert axis for MoE

    @property
    def world(self) -> int:
        return self.tp_size * self.dp_size * self.pp_size * self.sp_size * self.ep_size


def build_mesh(parallel: ParallelConfig, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = parallel.world
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    p = parallel
    grid = np.asarray(devices[:n]).reshape(
        p.dp_size, p.pp_size, p.sp_size, p.ep_size, p.tp_size
    )
    return Mesh(grid, axis_names=MESH_AXIS_ORDER)


@dataclass(frozen=True)
class LlamaShardings:
    """PartitionSpecs for the llama param tree + KV cache + activations.

    Megatron-style TP: column-parallel wq/wk/wv/w_gate/w_up (output dim over
    tp), row-parallel wo/w_down (input dim over tp) — one all-reduce per
    block, inserted by XLA from these specs.
    """

    mesh: Mesh

    @property
    def _pp(self):
        """Layer axis: sharded over pp when pipeline stages are configured
        (parallel/pipeline.py reshapes [L, ...] -> [S, L/S, ...] in-program;
        a leading-'pp' layout on L is the same placement)."""
        return PP_AXIS if self.mesh.shape.get(PP_AXIS, 1) > 1 else None

    def param_specs(self) -> dict:
        pp = self._pp
        return {
            "embed": P(None, TP_AXIS),  # hidden sharded
            "layers": {
                "attn_norm": P(pp),
                "wq": P(pp, None, TP_AXIS),  # [L, H, q_dim/tp]
                "wk": P(pp, None, TP_AXIS),
                "wv": P(pp, None, TP_AXIS),
                "wo": P(pp, TP_AXIS, None),  # row-parallel
                "mlp_norm": P(pp),
                "w_gate": P(pp, None, TP_AXIS),
                "w_up": P(pp, None, TP_AXIS),
                "w_down": P(pp, TP_AXIS, None),
            },
            "final_norm": P(None),
            "lm_head": P(None, TP_AXIS),  # vocab sharded on output
        }

    def param_shardings(self) -> dict:
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.param_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )

    def kv_sharding(self) -> NamedSharding:
        # [layers, pages, page_size, kv_heads*head_dim]: the lane axis over
        # tp in contiguous blocks of (kv_heads/tp)*head_dim, so a shard
        # holds whole heads (kv_heads % tp == 0); layers over pp when
        # pipelining (each stage owns its layers' pool)
        return NamedSharding(self.mesh, P(self._pp, None, None, TP_AXIS))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


@dataclass(frozen=True)
class MoeShardings(LlamaShardings):
    """LlamaShardings with the MLP rows replaced by expert weights sharded
    over the ``ep`` axis (wide-EP, SURVEY.md §2.5 row "Expert parallel");
    models/moe.py constrains the dispatched [E, C, H] token tensor to
    P("ep") so GSPMD inserts the all-to-all over ICI."""

    def param_specs(self) -> dict:
        specs = super().param_specs()
        pp = self._pp
        layers = dict(specs["layers"])
        layers.update(
            {
                "router": P(pp, None, None),  # [L, H, E]
                "w_gate": P(pp, EP_AXIS, None, TP_AXIS),  # [L, E, H, I/tp]
                "w_up": P(pp, EP_AXIS, None, TP_AXIS),
                "w_down": P(pp, EP_AXIS, TP_AXIS, None),
            }
        )
        specs["layers"] = layers
        return specs


@dataclass(frozen=True)
class DpAttentionShardings(MoeShardings):
    """DeepSeek-style wide-EP serving layout (reference recipe:
    recipes/deepseek-r1/sglang-wideep/tep16p-dep16d-disagg.yaml
    `--enable-dp-attention --ep-size 16`): experts are ep-sharded as in
    MoeShardings, but the KV cache is DATA-parallel over the ep axis — the
    page pool is sharded over ``ep`` so attention state is partitioned
    across the expert group instead of replicated on every rank (the KV
    memory blow-up dp-attention exists to avoid). GSPMD partitions the
    page gathers/writes across the ep group from this one spec; expert
    dispatch keeps its all-to-all over the same axis."""

    def kv_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self._pp, EP_AXIS, None, TP_AXIS))


def shard_params(params: dict, shardings) -> dict:
    """Place a param pytree onto the mesh (works for freshly-initialized,
    loaded, or int8-quantized params — a quantized leaf's scale gets the
    leaf's sharding with singleton axes unsharded)."""
    from ..models.quant import is_quant, scale_sharding

    shard_tree = shardings.param_shardings()

    def place(x, s):
        if x is None:
            return None
        if is_quant(x):
            return {
                "q": jax.device_put(x["q"], s),
                "s": jax.device_put(x["s"], scale_sharding(s, x["s"].shape)),
            }
        return jax.device_put(x, s)

    return jax.tree.map(
        place, params, shard_tree, is_leaf=lambda x: x is None or is_quant(x)
    )
