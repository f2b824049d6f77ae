"""A catalog deployment's raw instance fields, from its configuration file.

`bench/configs/mla-moe-catalog.json` names each model by its published
`config.json` (DeepSeek-V2/V3 block: latent attention, sparse experts),
each GPU tier by its datasheet, and its query types by the paper's
Table-6 draw.  `catalog_fields` turns that into the raw fields of the
planner's `Instance`, with the row arithmetic done here, so that the
benchmark's data cannot move when the program's own derivation
(`repro.core.catalog`) changes:

- `B`: total parameters x 2 bytes, GB; `B_act`: the parameters a token
  reads x 2 bytes (attention, dense layers, `num_experts_per_tok` +
  `n_shared_experts` experts, router, output head); the multi-token
  prediction module is left out, as it is not on the served decode path;
- `beta`: the latent KV cache, (`kv_lora_rank` + `qk_rope_head_dim`)
  x layers x 2 bytes per token, in KB (1e3 bytes);
- `e_base`: the Table-6 quality curve at the dense-equivalent size
  sqrt(B * B_act) GB, times each type's difficulty.
"""
from __future__ import annotations

import numpy as np

from .instances import _BASE_TYPES

BYTES_PER_PARAM = 2.0


def table6_types(I: int, seed: int) -> dict:
    """The query types of `instances.table6_synthetic(I, J, K, seed)`: the
    same generator, the same draws in the same order.  Per-type difficulty
    and the storage price are drawn next from the same generator, where
    Table 6 draws its model sizes."""
    rng = np.random.default_rng(seed)
    qi = rng.integers(0, 6, size=I)
    base = {k: np.asarray(v)[qi] for k, v in _BASE_TYPES.items()}
    out = dict(lam=base["lam"] * rng.uniform(0.7, 1.3, I),
               h=base["h"] * rng.uniform(0.8, 1.2, I),
               f=base["f"] * rng.uniform(0.8, 1.2, I),
               theta=base["theta"] * rng.uniform(0.9, 1.1, I),
               Delta=base["Delta"] * rng.uniform(0.9, 1.3, I),
               eps=base["eps"] * rng.uniform(0.9, 1.4, I),
               rho=base["rho"], phi=base["phi"], tau=base["tau"])
    out["difficulty"] = rng.uniform(0.8, 1.15, I)
    out["p_s"] = float(rng.uniform(0.0005, 0.001))
    return out


def mla_moe_params(c: dict) -> tuple[int, int]:
    """(total, active per token) parameters of one DeepSeek-V2/V3 block
    config, multi-token prediction left out."""
    H, L, heads = c["hidden_size"], c["num_hidden_layers"], \
        c["num_attention_heads"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    kv, qr = c["kv_lora_rank"], c.get("q_lora_rank")
    q_proj = (H * qr + qr + qr * heads * (nope + rope)) if qr \
        else H * heads * (nope + rope)
    kv_proj = H * (kv + rope) + kv + kv * heads * (nope + v)
    attention = q_proj + kv_proj + heads * v * H + 2 * H
    mlp = 3 * H * c["intermediate_size"]
    expert = 3 * H * c["moe_intermediate_size"]
    E = c["n_routed_experts"]
    router = H * E + (E if c.get("topk_method") == "noaux_tc" else 0)
    shared = c.get("n_shared_experts") or 0
    first = c.get("first_k_dense_replace", 0)
    freq = c.get("moe_layer_freq", 1)
    moe_layers = sum(1 for i in range(L) if i >= first and i % freq == 0)
    embed = c["vocab_size"] * H
    common = L * attention + (L - moe_layers) * mlp + embed + H
    total = common + embed + moe_layers * ((E + shared) * expert + router)
    active = common + moe_layers * ((c["num_experts_per_tok"] + shared)
                                    * expert + router)
    return total, active


def model_rows(config: dict) -> dict:
    """Per-row arrays of the catalog: names, B, B_act (GB), beta (KB)."""
    names, B, B_act, beta = [], [], [], []
    for m in config["models"]:
        c = m.get("config", config)
        total, active = mla_moe_params(c)
        names.append(m["name"])
        B.append(total * BYTES_PER_PARAM / 1e9)
        B_act.append(active * BYTES_PER_PARAM / 1e9)
        beta.append((c["kv_lora_rank"] + c["qk_rope_head_dim"])
                    * c["num_hidden_layers"] * BYTES_PER_PARAM / 1e3)
    return dict(model_names=names, B=np.array(B), B_act=np.array(B_act),
                beta=np.array(beta))


def catalog_fields(config: dict) -> dict:
    """Raw `Instance` fields of the configuration's deployment."""
    t = config["types"]
    types = table6_types(int(t["I"]), int(t["seed"]))
    rows = model_rows(config)
    tiers = {"tier_names": [], "C_gpu": [], "P_gpu": [], "p_c": [],
             "BW": [], "nu": [], "mu": []}
    for prec, pf in config["tiers"]["precisions"].items():
        for s in config["tiers"]["skus"]:
            tiers["tier_names"].append(f"{s['name']}-{prec}")
            tiers["C_gpu"].append(s["mem_gb"])
            tiers["P_gpu"].append(s["tflops"])
            tiers["p_c"].append(s["usd_per_h"] * pf["price"])
            tiers["BW"].append(s["hbm_gbps"])
            tiers["nu"].append(pf["nu"])
            tiers["mu"].append(pf["mu"])
    size = np.sqrt(rows["B"] * rows["B_act"])
    quality = 0.049 * (size / 2.0) ** -0.75 + 0.006
    I = int(t["I"])
    plan = config["plan"]
    return dict(
        query_names=[f"q{i}" for i in range(I)], **rows,
        **{k: (v if k == "tier_names" else np.array(v, float))
           for k, v in tiers.items()},
        tp_degrees=list(config["lattice"]["tp_degrees"]),
        pp_depths=list(config["lattice"]["pp_depths"]),
        lam=types["lam"], h=types["h"], f=types["f"], theta=types["theta"],
        e_base=types["difficulty"][:, None] * quality[None, :],
        Delta=types["Delta"], eps=types["eps"], rho=types["rho"],
        phi=types["phi"], zeta=np.ones(I), p_s=types["p_s"],
        delta=float(plan["delta"]), C_s=float(plan["C_s"]),
        tau=types["tau"])
