"""Deployments and per-call inputs, made from seeds by the benchmark itself.

`table6_synthetic` is the paper's Table-6 synthetic instance generator
(arXiv:2604.07472 §5.1, Azure-trace-calibrated base types), copied here
so that the benchmark's data cannot move when the program's own copy
(`repro.core.instance.random_instance`) changes.  It returns the raw
fields of the planner's `Instance`; nothing derived.

`InputPool` gives call *i* of a run its inputs.  The inputs live in a
fixed pool drawn from the traffic file's `pool_seed`; a run's `--seed`
permutes the pool within consecutive blocks of `block` entries.  So
every seed sends the same set of inputs in another order, no two calls
of a run repeat an input, and warm-up draws from a stream of its own.
"""
from __future__ import annotations

import numpy as np

# Base query types of the paper's calibration (Azure LLM Inference Trace
# ranges): arrival rate /h, input and output tokens, KB/token, delay SLO
# s, error SLO, $/ms delay penalty, $/h unmet penalty, overhead factor.
_BASE_TYPES = {
    "lam": [18000.0, 15000.0, 12000.0, 8000.0, 2500.0, 1500.0],
    "h": [2000.0, 512.0, 800.0, 300.0, 100.0, 150.0],
    "f": [200.0, 800.0, 600.0, 700.0, 1200.0, 2500.0],
    "theta": [5.0, 4.0, 6.0, 4.5, 25.0, 40.0],
    "Delta": [2.5, 1.5, 2.0, 5.0, 16.0, 25.0],
    "eps": [0.05, 0.02, 0.04, 0.03, 0.0155, 0.08],
    "rho": [2e-4, 3e-4, 1e-4, 6e-4, 7e-4, 1e-3],
    "phi": [600.0, 750.0, 500.0, 700.0, 1200.0, 1500.0],
    "tau": [1.0, 0.9, 0.95, 1.1, 1.2, 1.3],
}
# Hardware (memory GB, TFLOP/s, HBM GB/s, $/h at FP16), in this order.
_GPU_HW = (
    ("RTX4090", 24.0, 82.6, 1008.0, 0.35),
    ("A6000", 48.0, 40.7 * 2, 768.0, 0.80),
    ("A100-40", 40.0, 312.0, 1555.0, 1.20),
    ("H100-80", 80.0, 1484.0, 3350.0, 2.50),
)
_PRECISIONS = ("FP16", "INT8", "INT4")
_NU = {"FP16": 1.0, "INT8": 0.5, "INT4": 0.25}
_MU = {"FP16": 1.0, "INT8": 1.15, "INT4": 1.35}
_PRICE = {"FP16": 1.0, "INT8": 0.9, "INT4": 0.85}


def table6_synthetic(I: int, J: int, K: int, seed: int,
                     budget: float | None = None) -> dict:
    """Raw `Instance` fields of one synthetic (I, J, K) deployment."""
    rng = np.random.default_rng(seed)
    qi = rng.integers(0, 6, size=I)
    base = {k: np.asarray(v)[qi] for k, v in _BASE_TYPES.items()}
    lam = base["lam"] * rng.uniform(0.7, 1.3, I)
    h = base["h"] * rng.uniform(0.8, 1.2, I)
    f = base["f"] * rng.uniform(0.8, 1.2, I)
    theta = base["theta"] * rng.uniform(0.9, 1.1, I)
    Delta = base["Delta"] * rng.uniform(0.9, 1.3, I)
    eps = base["eps"] * rng.uniform(0.9, 1.4, I)
    # Model catalog: log-spaced sizes 1B..70B, KV footprint and base
    # error rate following size.
    B = np.sort(np.exp(rng.uniform(np.log(2.0), np.log(140.0), J)))
    beta = 31.0 + (305.0 - 31.0) * (B - B.min()) / max(B.max() - B.min(),
                                                        1e-9)
    quality = 0.049 * (B / 2.0) ** -0.75 + 0.006
    difficulty = rng.uniform(0.8, 1.15, I)
    tiers = {"names": [], "C_gpu": [], "P_gpu": [], "p_c": [], "BW": [],
             "nu": [], "mu": []}
    for t in range(K):
        hw, mem, tflops, bw, price = _GPU_HW[t % len(_GPU_HW)]
        prec = _PRECISIONS[(t // len(_GPU_HW)) % 3]
        tiers["names"].append(f"{hw}-{prec}-{t}")
        tiers["C_gpu"].append(mem)
        tiers["P_gpu"].append(tflops * rng.uniform(0.9, 1.1))
        tiers["p_c"].append(price * rng.uniform(0.85, 1.15) * _PRICE[prec])
        tiers["BW"].append(bw * rng.uniform(0.95, 1.05))
        tiers["nu"].append(_NU[prec])
        tiers["mu"].append(_MU[prec])
    return dict(
        query_names=[f"q{i}" for i in range(I)],
        model_names=[f"m{j}" for j in range(J)],
        tier_names=tiers["names"], tp_degrees=[1, 2, 4, 8],
        pp_depths=[1, 2, 4], lam=lam, h=h, f=f, theta=theta, B=B,
        beta=beta, e_base=difficulty[:, None] * quality[None, :],
        C_gpu=np.array(tiers["C_gpu"]), P_gpu=np.array(tiers["P_gpu"]),
        p_c=np.array(tiers["p_c"]), BW=np.array(tiers["BW"]),
        nu=np.array(tiers["nu"]), mu=np.array(tiers["mu"]),
        Delta=Delta, eps=eps, rho=base["rho"], phi=base["phi"],
        zeta=np.ones(I), p_s=float(rng.uniform(0.0005, 0.001)),
        delta=100.0 * I / 6.0 if budget is None else budget,
        C_s=1000.0 * I / 6.0, tau=base["tau"])


GENERATORS = {"table6_synthetic": table6_synthetic}


def make_fields(config: dict) -> dict:
    """The raw instance fields a configuration file names."""
    gen = config["generator"]
    return GENERATORS[gen["name"]](**gen["args"])


def _check_seed(seed: int) -> int:
    if not isinstance(seed, int) or seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed!r}")
    return seed


class InputPool:
    """Per-call input indices: the pool permuted within blocks by seed."""

    WINDOW, WARMUP = 0, 1

    def __init__(self, pool_seed: int, block: int, seed: int):
        self.pool_seed = pool_seed
        self.block = block
        self.seed = _check_seed(seed)
        self._perms: dict[int, np.ndarray] = {}

    def index(self, i: int) -> int:
        """Pool entry sent by window call `i` (0-based)."""
        b, r = divmod(i, self.block)
        if b not in self._perms:
            rng = np.random.default_rng([self.seed, b])
            self._perms[b] = rng.permutation(self.block)
        return b * self.block + int(self._perms[b][r])


def entry_rng(pool_seed: int, entry: int, stream: int = InputPool.WINDOW
              ) -> np.random.Generator:
    """The generator that draws one pool entry's inputs."""
    return np.random.default_rng([pool_seed, stream, entry])


def entry_seed(pool_seed: int, entry: int, stream: int = InputPool.WINDOW
               ) -> int:
    """A 31-bit seed for an entry point that takes one (`seed=`)."""
    return int(entry_rng(pool_seed, entry, stream).integers(0, 2**31 - 1))
