"""Model rows of the planner's catalog from published model configs.

A row of `Instance` is a model's resident weights `B` (GB at FP16), the
weights a token reads `B_act` (the same for a dense model, the active
experts' share for a sparse one), its KV-cache footprint `beta` (KB per
token) and its FP16 base error rate, from the paper's size curve.
`mla_moe_row` derives the first three from a DeepSeek-V2/V3-style
`config.json` (latent attention, MLA; sparse experts, MoE);
`with_model_rows` puts rows in place of an instance's model catalog.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from .instance import KB_PER_GB, Instance

BYTES_PER_PARAM = 2.0           # FP16/BF16 weights and cache entries


@dataclasses.dataclass(frozen=True)
class ModelRow:
    """One model of the catalog, in the planner's units."""
    name: str
    B: float                        # resident weights, GB at FP16
    beta: float                     # KV cache, KB per token
    B_act: float | None = None      # weights read per token, GB; None = B

    @property
    def base_error(self) -> float:
        """FP16 base error rate on a type of mean difficulty: the paper's
        quality curve of a dense model of B GB (Table-6 generator,
        `random_instance`), 0.049 (B/2)^-0.75 + 0.006, taken at the
        dense-equivalent size sqrt(B * B_act)."""
        b_act = self.B if self.B_act is None else self.B_act
        return 0.049 * (np.sqrt(self.B * b_act) / 2.0) ** -0.75 + 0.006


def mla_moe_params(config: dict) -> tuple[int, int]:
    """(total, active per token) parameters of a DeepSeek-V2/V3 block model.

    Counted from the published config: embedding, output head, per layer
    the MLA projections (q down/up or one q projection, the joint KV down
    projection with its rope part, KV up, output) and norms, the first
    `first_k_dense_replace` layers' dense SwiGLU of `intermediate_size`,
    and in the others `n_routed_experts` + `n_shared_experts` SwiGLU
    experts of `moe_intermediate_size` with the router (and its score
    bias under `noaux_tc`).  Active per token: everything but the input
    embedding (a lookup reads one row) and the routed experts not chosen,
    so `num_experts_per_tok` + `n_shared_experts` experts per layer.

    Departures: the multi-token-prediction module
    (`num_nextn_predict_layers`) is left out, as it is not on the served
    decode path; keys outside this block (gated attention, q/k norms of
    some derivatives) are not read.
    """
    c = config
    H, L, heads = c["hidden_size"], c["num_hidden_layers"], \
        c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    kv_rank, q_rank = c["kv_lora_rank"], c.get("q_lora_rank")
    q = (H * q_rank + q_rank + q_rank * heads * qk if q_rank
         else H * heads * qk)
    attn = (q + H * (kv_rank + c["qk_rope_head_dim"]) + kv_rank
            + kv_rank * heads * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + heads * c["v_head_dim"] * H + 2 * H)
    dense = 3 * H * c["intermediate_size"]
    expert = 3 * H * c["moe_intermediate_size"]
    E = c["n_routed_experts"]
    shared = c.get("n_shared_experts") or 0
    router = H * E + (E if c.get("topk_method") == "noaux_tc" else 0)
    first, freq = c.get("first_k_dense_replace", 0), c.get("moe_layer_freq", 1)
    n_moe = sum(1 for i in range(L) if i >= first and i % freq == 0)
    n_dense = L - n_moe
    head = c["vocab_size"] * H + H              # output head, final norm
    body = L * attn + n_dense * dense + head
    total = body + n_moe * ((E + shared) * expert + router) \
        + c["vocab_size"] * H
    active = body + n_moe * ((c["num_experts_per_tok"] + shared) * expert
                             + router)
    return total, active


def mla_moe_row(name: str, config: dict) -> ModelRow:
    """The catalog row of a DeepSeek-V2/V3 block model (see
    `mla_moe_params`): B and B_act at 2 bytes per parameter, beta the
    latent KV cache, (kv_lora_rank + qk_rope_head_dim) entries of 2 bytes
    per layer and token.

    The planner's model around the row departs from the architecture in
    two places.  d_comp stays the paper's batch-1 decode roofline, so a
    token reads the active bytes, although at large decode batches more
    of the routed experts are read per step.  d_comm keeps the paper's
    beta * 8 activation proxy.
    """
    total, active = mla_moe_params(config)
    kv_bytes = ((config["kv_lora_rank"] + config["qk_rope_head_dim"])
                * config["num_hidden_layers"] * BYTES_PER_PARAM)
    return ModelRow(name=name, B=total * BYTES_PER_PARAM / 1e9,
                    B_act=active * BYTES_PER_PARAM / 1e9,
                    beta=kv_bytes / (1e9 / KB_PER_GB))


def with_model_rows(inst: Instance, rows: Sequence[ModelRow]) -> Instance:
    """A copy of `inst` that serves `rows` in place of its model catalog.

    Types keep their relative difficulty: type i's FP16 base error on row
    j is `rows[j].base_error` times the mean of type i's old row of
    `e_base` over the mean of all of it (exact for the generators'
    difficulty x quality tables).
    """
    per_type = inst.e_base.mean(axis=1)
    difficulty = per_type / per_type.mean()
    quality = np.array([r.base_error for r in rows])
    B = np.array([r.B for r in rows], float)
    return dataclasses.replace(
        inst, model_names=[r.name for r in rows], B=B,
        B_act=np.array([r.B if r.B_act is None else r.B_act for r in rows]),
        beta=np.array([r.beta for r in rows], float),
        e_base=difficulty[:, None] * quality[None, :], kv_applicable=None)
