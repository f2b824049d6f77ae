"""Device-resident instance tensors for the XLA engine.

One `XlaInstanceTensors` bundle per `Instance`, cached on the instance
itself (`inst._xla_tensors`; the perturbed()/stressed()/with_lam()
helpers build fresh Instance objects, so a cached bundle can never go
stale).  Every tensor is a flat ``[I, J*K]`` (or ``[J*K]``) float64 view
of a precomputed numpy tensor the numpy engine already uses — the host
arrays are the source of truth, the device copies are uploaded once and
reused by every jitted kernel call of every solve on the instance.

float64 is non-negotiable: the numpy oracle runs in float64, and the
engine's <=-objective contract against it leaves no room for float32
rounding in the ranking keys.  jax defaults to float32, so x64 mode is
enabled here, at first import of the lazy xla tier — before any kernel
is traced.  (Pallas kernels elsewhere in the repo pin their own dtypes
explicitly and are unaffected by the global flag.)
"""
from __future__ import annotations

import numpy as np

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402  (after the x64 switch, deliberately)

from ...spans import span  # noqa: E402
from ..instance import Instance  # noqa: E402


class XlaInstanceTensors:
    """Flat [I, J*K] device tensors shared by the phase-2 ranking kernel
    and the relocate screen kernel.

    The derived products (`psb_data`, `rho_d`) are computed with numpy in
    exactly the elementwise op order of `rank_keys_all`, so the values
    shipped to the device match the oracle's intermediate grids bitwise;
    any remaining divergence comes only from XLA's instruction fusion on
    the final arithmetic (last-ulp), which the engine's tolerance /
    screen-slack policy absorbs.
    """

    def __init__(self, inst: Instance):
        I, J, K = inst.I, inst.J, inst.K
        JK = J * K
        self.inst = inst
        self.JK = JK
        m1_delay = inst.m1_delay.reshape(I, JK)
        f64, b8 = jnp.float64, jnp.bool_
        # --- shared by both kernels -----------------------------------
        self.m1_delay = jnp.asarray(m1_delay, dtype=f64)
        self.m1_valid = jnp.asarray(inst.m1_feasible.reshape(I, JK),
                                    dtype=b8)
        self.ebf = jnp.asarray(inst.e_bar_floor_flat, dtype=f64)
        self.eps = jnp.asarray(inst.eps, dtype=f64)
        self.Delta = jnp.asarray(inst.Delta, dtype=f64)
        self.Delta_T = float(inst.Delta_T)
        # --- phase-2 ranking (rank_keys_all's cost pieces) ------------
        # Cost term p_s * (B_j + data_gb_i), elementwise in the oracle's
        # own op order (add, then scale).
        B_jk = np.repeat(inst.B, K)
        self.psb_data = jnp.asarray(
            inst.p_s * (B_jk[None, :] + inst.data_gb[:, None]), dtype=f64)
        # Routed-delay cost rho_i * d * 1e3 at the M1 winner (active
        # cells are overridden per call).
        self.rho_d = jnp.asarray((inst.rho[:, None] * m1_delay) * 1e3,
                                 dtype=f64)
        self.m1_nm = jnp.asarray(inst.m1_nm.reshape(I, JK).astype(float),
                                 dtype=f64)
        self.pc_flat = jnp.asarray(np.tile(inst.p_c, J), dtype=f64)
        # --- relocate screen (DestCache row ingredients) --------------
        self.m1_rental = jnp.asarray(inst.m1_rental.reshape(I, JK),
                                     dtype=f64)
        self.lpx = jnp.asarray(inst.load_per_x_flat, dtype=f64)
        self.psB_flat = jnp.asarray(np.repeat(inst.p_s_B, K), dtype=f64)
        self.comp_flat = jnp.asarray(np.tile(inst.comp_cap_coef, J),
                                     dtype=f64)


def tensors_for(inst: Instance) -> XlaInstanceTensors:
    """The instance's cached tensor bundle, built on first use."""
    with span("xla.tensors"):
        if inst._xla_tensors is None:
            inst._xla_tensors = XlaInstanceTensors(inst)
        return inst._xla_tensors
