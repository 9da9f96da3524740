"""Domain-decomposed finite-difference incompressible solver (2-D).

The PHASTA-shaped producer the paper couples to: a structured-grid
Navier–Stokes solver whose state is decomposed over a ``space`` mesh axis
and advanced *inside one* ``shard_map`` — each step touches only its own
subdomain rows plus a width-1/width-2 halo moved by
:func:`~.halo.halo_exchange` (``lax.ppermute``), never a global
collective.  Feeding the in-situ data plane, its snapshots are emitted
**shard-local** too: the producer's ``elem_sharding`` carries the
``space`` axis through ``core.store.capture_scan`` so the put is a local
slab update on every shard (the ``capture_scan_sharded`` tier of
``insitu.plan``).

Numerics — Chorin projection on a periodic ``n x n`` collocated grid
(``h = 2*pi/n``), rows (dim 0) decomposed over the mesh:

1. explicit advection + diffusion with central differences →
   ``(u*, v*)``;
2. pressure Poisson ``L phi = div(u*, v*) / dt`` solved by
   ``jacobi_iters`` Jacobi sweeps of the *wide* Laplacian
   ``L = Dx Dx + Dy Dy`` (the operator consistent with the
   central-difference divergence, so the projection annihilates exactly
   the divergence the corrector measures);
3. correction ``u = u* - dt * Dx phi`` (central gradient).

The discrete Taylor–Green vortex is an exact eigenfunction of this
scheme: its central-difference advection term is an exact discrete
gradient (projected away completely), leaving pure diffusive decay at
the *discrete* rate ``g = 1 - 2 nu dt lambda_h`` per step with
``lambda_h = 4 sin^2(h/2) / h^2`` — the analytic validation the tests
pin to fp32 tightness, alongside the continuum ``exp(-4 nu t)`` rate the
paper-level comparison against ``sim.spectral`` uses.

The sharded and single-device paths share one stencil kernel
(:func:`_advance`), parameterized only by the exchange function — the
reference pads the global array (:func:`~.halo.pad_reference`), the
sharded step pads each block via ppermute — so their outputs agree to
fp32 roundoff at any shard count.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .halo import halo_exchange, pad_reference

__all__ = ["FDConfig", "FDState", "taylor_green", "decaying_turbulence",
           "make_step", "make_producer", "shard_state",
           "taylor_green_factor", "energy", "max_divergence", "snapshot"]


@dataclass(frozen=True)
class FDConfig:
    """Static solver configuration (grid, fluid, time step, Poisson)."""

    n: int = 32               # grid points per side (periodic box 2*pi)
    nu: float = 0.01          # kinematic viscosity
    dt: float = 2e-3          # explicit Euler time step
    jacobi_iters: int = 64    # pressure Poisson sweeps per step

    def __post_init__(self):
        if self.n < 4:
            raise ValueError("n must be >= 4")
        if self.nu <= 0:
            raise ValueError("nu must be > 0")
        if self.dt <= 0:
            raise ValueError("dt must be > 0")
        if self.jacobi_iters < 1:
            raise ValueError("jacobi_iters must be >= 1")

    @property
    def h(self) -> float:
        return 2.0 * np.pi / self.n

    @property
    def shape(self) -> tuple[int, int]:
        return (self.n, self.n)

    def validate_shards(self, n_shards: int, axis: str = "space") -> None:
        """Fail fast on a grid/mesh mismatch: a non-dividing decomposition
        would otherwise surface deep inside ``shard_map`` as an opaque
        sharding error."""
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        if self.n % n_shards != 0:
            raise ValueError(
                f"grid rows n={self.n} do not divide over the "
                f"{n_shards}-shard {axis!r} mesh axis: each shard must own "
                f"an equal n/{n_shards} row block — pick n a multiple of "
                f"the shard count (e.g. n={self.n - self.n % n_shards or n_shards * 4})")


class FDState(NamedTuple):
    """Solver state: velocity fields plus the clock (a pytree)."""

    u: jax.Array      # [n, n] x-velocity
    v: jax.Array      # [n, n] y-velocity
    t: jax.Array      # f32 scalar: physical time
    step: jax.Array   # i32 scalar: step count


# ---------------------------------------------------------------------------
# Initializers (built on the full grid; shard with jax.device_put after)
# ---------------------------------------------------------------------------

def _grid(cfg: FDConfig):
    x = jnp.arange(cfg.n, dtype=jnp.float32) * cfg.h
    return jnp.meshgrid(x, x, indexing="ij")


def taylor_green(cfg: FDConfig) -> FDState:
    """The 2-D Taylor–Green vortex ``u = cos x sin y, v = -sin x cos y``
    — exactly divergence-free under central differences, and the scheme's
    analytic decay benchmark (see module docstring)."""
    X, Y = _grid(cfg)
    return FDState(u=jnp.cos(X) * jnp.sin(Y), v=-jnp.sin(X) * jnp.cos(Y),
                   t=jnp.zeros((), jnp.float32),
                   step=jnp.zeros((), jnp.int32))


def decaying_turbulence(cfg: FDConfig, key, e0: float = 0.5,
                        k_peak: float = 4.0) -> FDState:
    """Decaying-HIT initial condition: a random band-limited
    streamfunction ``psi`` with energy peaked near ``k_peak``, velocities
    ``u = Dy psi, v = -Dx psi`` via the same central differences the
    solver uses — so the field is *exactly* discretely divergence-free —
    normalized to kinetic energy ``e0``."""
    kx = jnp.fft.fftfreq(cfg.n, d=1.0 / cfg.n)
    k2 = kx[:, None] ** 2 + kx[None, :] ** 2
    k = jnp.sqrt(k2)
    # band-limited von-Karman-ish spectrum; cut above n/4 to keep the
    # collocated projection's resolvable band (the wide Laplacian is
    # blind to the Nyquist checkerboard)
    amp = (k ** 2) * jnp.exp(-((k / k_peak) ** 2))
    amp = jnp.where((k > 0) & (k <= cfg.n / 4), amp, 0.0)
    noise = jax.random.normal(key, (cfg.n, cfg.n))
    psi = jnp.real(jnp.fft.ifft2(jnp.fft.fft2(noise) * amp)
                   ).astype(jnp.float32)
    h = cfg.h
    u = (jnp.roll(psi, -1, 1) - jnp.roll(psi, 1, 1)) / (2 * h)
    v = -(jnp.roll(psi, -1, 0) - jnp.roll(psi, 1, 0)) / (2 * h)
    e = 0.5 * jnp.mean(u * u + v * v)
    scale = jnp.sqrt(e0 / jnp.maximum(e, 1e-30))
    return FDState(u=u * scale, v=v * scale,
                   t=jnp.zeros((), jnp.float32),
                   step=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# The shared stencil kernel (exchange-parameterized)
# ---------------------------------------------------------------------------

def _advance(cfg: FDConfig, state: FDState, exchange) -> FDState:
    """One Chorin-projection step.  ``exchange(f, width)`` pads ``f``
    with ``width`` halo rows along dim 0 — the ONLY place shard topology
    enters; columns (dim 1) are whole on every shard, so their taps are
    local rolls."""
    h, dt, nu = cfg.h, cfg.dt, cfg.nu
    u, v = state.u, state.v

    def derivs(f):
        fp = exchange(f, 1)
        fx = (fp[2:] - fp[:-2]) / (2 * h)
        fxx = (fp[2:] - 2.0 * f + fp[:-2]) / (h * h)
        fy = (jnp.roll(f, -1, 1) - jnp.roll(f, 1, 1)) / (2 * h)
        fyy = (jnp.roll(f, -1, 1) - 2.0 * f + jnp.roll(f, 1, 1)) / (h * h)
        return fx, fy, fxx, fyy

    ux, uy, uxx, uyy = derivs(u)
    vx, vy, vxx, vyy = derivs(v)
    us = u + dt * (-(u * ux + v * uy) + nu * (uxx + uyy))
    vs = v + dt * (-(u * vx + v * vy) + nu * (vxx + vyy))

    # divergence of the provisional field (central differences)
    usp = exchange(us, 1)
    div = (usp[2:] - usp[:-2]) / (2 * h) \
        + (jnp.roll(vs, -1, 1) - jnp.roll(vs, 1, 1)) / (2 * h)
    rhs = div / dt

    # Jacobi on the wide Laplacian Dx Dx + Dy Dy (diagonal -1/h^2):
    # phi <- (phi_{i+2} + phi_{i-2} + phi_{j+2} + phi_{j-2}) / 4 - h^2 rhs
    def sweep(_, phi):
        pp = exchange(phi, 2)
        px = pp[4:] + pp[:-4]
        py = jnp.roll(phi, -2, 1) + jnp.roll(phi, 2, 1)
        return (px + py) * 0.25 - (h * h) * rhs

    phi = lax.fori_loop(0, cfg.jacobi_iters, sweep, jnp.zeros_like(us))

    pp = exchange(phi, 1)
    u_new = us - dt * (pp[2:] - pp[:-2]) / (2 * h)
    v_new = vs - dt * (jnp.roll(phi, -1, 1) - jnp.roll(phi, 1, 1)) / (2 * h)
    return FDState(u=u_new, v=v_new, t=state.t + dt, step=state.step + 1)


def make_step(cfg: FDConfig, mesh: Mesh | None = None,
              axis: str = "space"):
    """Build the jitted step ``state -> state``.

    ``mesh=None``: the single-device reference (global-array periodic
    padding).  With a mesh, the step runs inside ONE ``shard_map`` with
    rows partitioned over ``axis`` and every stencil tap fed by
    :func:`~.halo.halo_exchange` — after validating the grid divides the
    mesh (the fail-fast half of the sharding contract)."""
    if mesh is None:
        def exchange(f, width):
            return pad_reference(f, width=width, dim=0)

        return jax.jit(lambda state: _advance(cfg, state, exchange))

    cfg.validate_shards(int(mesh.shape[axis]), axis)
    from jax import shard_map

    def exchange(f, width):
        return halo_exchange(f, axis=axis, width=width, dim=0,
                             boundary="periodic")

    specs = FDState(u=P(axis, None), v=P(axis, None), t=P(), step=P())
    body = shard_map(lambda state: _advance(cfg, state, exchange),
                     mesh=mesh, in_specs=(specs,), out_specs=specs,
                     check_vma=False)
    return jax.jit(body)


def shard_state(state: FDState, mesh: Mesh, axis: str = "space") -> FDState:
    """Place a full-grid state row-decomposed over ``axis`` (fields
    sharded, clock replicated)."""
    field = NamedSharding(mesh, P(axis, None))
    scalar = NamedSharding(mesh, P())
    return FDState(u=jax.device_put(state.u, field),
                   v=jax.device_put(state.v, field),
                   t=jax.device_put(state.t, scalar),
                   step=jax.device_put(state.step, scalar))


def make_producer(cfg: FDConfig, mesh: Mesh | None = None,
                  axis: str = "space", init: str = "taylor_green",
                  key=None):
    """Wire the solver into the in-situ data plane.

    Returns ``(step_fn, state0, elem_sharding)`` for a declarative
    ``insitu.Producer``: ``step_fn(carry, rank, t)`` advances one step
    and emits the stacked ``[2, n, n]`` velocity snapshot under a
    ``(rank 0, t)`` key; ``elem_sharding`` (``None`` off-mesh) carries
    the ``space`` axis into ``capture_scan`` so the emitted element is
    put shard-local — the ``capture_scan_sharded`` tier."""
    from ..core.store import make_key

    step = make_step(cfg, mesh, axis=axis)
    if init == "taylor_green":
        state0 = taylor_green(cfg)
    elif init == "decaying_turbulence":
        state0 = decaying_turbulence(
            cfg, key if key is not None else jax.random.key(0))
    else:
        raise ValueError(f"unknown init {init!r} (have "
                         f"('taylor_green', 'decaying_turbulence'))")
    elem_sharding = None
    if mesh is not None:
        state0 = shard_state(state0, mesh, axis)
        elem_sharding = NamedSharding(mesh, P(None, axis, None))

    def step_fn(carry, rank, t):
        nxt = step(carry)
        return nxt, make_key(0, t), jnp.stack([nxt.u, nxt.v])

    return step_fn, state0, elem_sharding


# ---------------------------------------------------------------------------
# Diagnostics
# ---------------------------------------------------------------------------

def taylor_green_factor(cfg: FDConfig) -> float:
    """Per-step velocity decay factor of the discrete Taylor–Green mode:
    ``1 - 2 nu dt lambda_h`` (energy decays as its square).  Approaches
    the continuum ``exp(-2 nu dt)`` as ``h -> 0`` (``lambda_h =
    (1 - h^2/12 + ...)``)."""
    lam = 4.0 * np.sin(cfg.h / 2.0) ** 2 / cfg.h ** 2
    return float(1.0 - 2.0 * cfg.nu * cfg.dt * lam)


@jax.jit
def energy(state: FDState) -> jax.Array:
    """Mean kinetic energy ``0.5 <u^2 + v^2>``."""
    return 0.5 * jnp.mean(state.u ** 2 + state.v ** 2)


@jax.jit
def snapshot(state: FDState) -> jax.Array:
    """The emitted table element: stacked ``[2, n, n]`` velocities."""
    return jnp.stack([state.u, state.v])


def max_divergence(cfg: FDConfig, state: FDState) -> jax.Array:
    """Max |central-difference divergence| — the invariant the projection
    maintains (down to the Jacobi residual)."""
    h = cfg.h
    div = (jnp.roll(state.u, -1, 0) - jnp.roll(state.u, 1, 0)) / (2 * h) \
        + (jnp.roll(state.v, -1, 1) - jnp.roll(state.v, 1, 1)) / (2 * h)
    return jnp.max(jnp.abs(div))
