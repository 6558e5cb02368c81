"""TickFuse: the fused FleetSim core.

The staged core advances one tick per ``lax.scan`` step with the whole
:class:`~repro.fleetsim.state.FleetState` as the int32/float32 carry.  This
core restructures the *execution* of the same tick — never its
semantics:

* **chunked scan** — an outer ``lax.scan`` advances ``K`` ticks per step
  (an inner scan over the exact staged tick), so the state stays resident
  across a whole chunk and only crosses the carry boundary once per ``K``
  ticks.  XLA donates the chunk carry buffers to the next step, so the
  packed state is updated in place across chunks;
* **dtype-packed carry** — the bounded integer state (queue ring
  ``head``/``count``, per-server StateT occupancy) is packed to the
  narrowest dtype its *static* bound fits (:func:`pick_count_dtype`:
  uint8 / int16, widening — never wrapping) at chunk boundaries and
  unpacked inside the chunk.  Integer round-trips within the bound are
  exact, so packing cannot change a single bit of the results.  REQ_ID
  carriers (spine ``seq``, filter tables, client dedup) stay int32.

The switch response path is whatever ``FleetConfig.filter_backend`` names,
as in the staged core: the TickFuse Pallas kernel
(``repro.kernels.tickfuse``: StateT write + fingerprint filter in one
launch) runs only for ``filter_backend="tickfuse"``, which nothing selects
by default.

Because every tick replays :func:`repro.fleetsim.stages.build_step`
verbatim — same PRNG draws, same op order — the fused core is
**bit-identical** to the staged core on the non-stage policy matrix
(enforced by ``tests/test_fused.py`` against the staged engine and the
checked-in goldens).  ``engine.select_core`` picks it on an accelerator
for configs without an optional stage (coordinator / hedge_timer /
telemetry / batch server); those run staged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.switch_jax import group_pairs_array
from repro.fleetsim.config import FleetConfig
from repro.fleetsim.stages import build_step, with_recovery_wipe
from repro.fleetsim.state import FleetState, init_fleet_state

#: K — ticks advanced per outer scan step (clipped to n_ticks)
DEFAULT_TICKS_PER_CHUNK = 512


# ------------------------------------------------------------ dtype packing --
def pick_count_dtype(bound: int):
    """The narrowest unsigned/signed integer dtype that exactly holds every
    count in ``[0, bound]`` — widening to int32 when the bound outgrows the
    narrow types and **raising** beyond int32, never wrapping.

    ``bound`` is a static shape-derived quantity (queue capacity, wheel
    width, …), so the choice is made once at trace time and a value that
    could overflow the packed dtype cannot exist by construction.
    """
    if bound < 0:
        raise ValueError(f"bound must be non-negative, got {bound}")
    for dt in (jnp.uint8, jnp.int16, jnp.int32):
        if bound <= jnp.iinfo(dt).max:
            return dt
    raise ValueError(
        f"bound {bound} exceeds int32; refusing to pack a counter that "
        "could silently wrap")


def pack_array(x: jax.Array, bound: int) -> jax.Array:
    """Pack a bounded non-negative int array to its narrowest exact dtype
    (see :func:`pick_count_dtype`); values are bounded by construction, so
    the cast is an exact round-trip."""
    return x.astype(pick_count_dtype(bound))


def pack_state(cfg: FleetConfig, state: FleetState) -> FleetState:
    """Dtype-pack the bounded integer carry between scan chunks.

    Packed fields and their static bounds (docs/architecture.md carries the
    full table): ``queues.head`` ≤ Q−1, ``queues.count`` ≤ Q, and the
    switch ``server_state`` (piggybacked queue length) ≤ Q.  Everything
    holding REQ_IDs, metrics, or float payloads is untouched.
    """
    q = cfg.queue_cap
    return state._replace(
        switch=state.switch._replace(
            server_state=pack_array(state.switch.server_state, q)),
        queues=state.queues._replace(
            head=pack_array(state.queues.head, max(q - 1, 0)),
            count=pack_array(state.queues.count, q)))


def unpack_state(state: FleetState) -> FleetState:
    """Widen the packed carry back to the int32 the stages compute in."""
    return state._replace(
        switch=state.switch._replace(
            server_state=state.switch.server_state.astype(jnp.int32)),
        queues=state.queues._replace(
            head=state.queues.head.astype(jnp.int32),
            count=state.queues.count.astype(jnp.int32)))


# ----------------------------------------------------------------- runner ---
def fused_core(cfg: FleetConfig, params, recovery_wipe: bool = True,
               chunk: int = DEFAULT_TICKS_PER_CHUNK) -> FleetState:
    """Advance one fabric for ``cfg.n_ticks`` ticks on the fused core.

    Chunks of ``K = min(chunk, n_ticks)`` ticks ride an outer ``lax.scan``
    whose carry is the dtype-packed state; each chunk unpacks, replays the
    exact staged tick ``K`` times (an inner scan over
    :func:`stages.build_step`), and repacks.  A remainder ``n_ticks mod K``
    runs as a staged tail — so any K yields bit-identical results, K only
    moves the pack points (the engine always uses the default).
    ``recovery_wipe`` opens each tick with the §3.6 wipe, as in the staged
    engine (``engine.needs_recovery_wipe``).
    """
    if cfg.coordinator or cfg.hedge_timer or cfg.telemetry:
        raise ValueError(
            "the fused core supports the always-on pipeline only; "
            "coordinator/hedge_timer/telemetry configs run staged "
            "(engine.select_core routes them there)")
    k = max(1, min(chunk, cfg.n_ticks))
    with jax.named_scope("fleetsim.init"):
        gp = group_pairs_array(cfg.n_servers)
        k_pois, k0 = jax.random.split(jax.random.PRNGKey(params.seed))
        state = init_fleet_state(cfg, k0)
        step = build_step(cfg, params, gp)
        if recovery_wipe:
            step = with_recovery_wipe(cfg, params, step)
    with jax.named_scope("fleetsim.draw"):
        ticks = jnp.arange(cfg.n_ticks, dtype=jnp.int32)
        if cfg.arrival == "trace":
            n_raw = params.arrival_counts.astype(jnp.int32)
        else:
            n_raw = jax.random.poisson(
                k_pois, params.rate_per_us * cfg.dt_us, (cfg.n_ticks,)
            ).astype(jnp.int32)

    n_chunks, n_tail = divmod(cfg.n_ticks, k)

    def chunk(packed, xs):
        with jax.named_scope("fleetsim.pack"):
            st = unpack_state(packed)
        st, _ = jax.lax.scan(step, st, xs)
        with jax.named_scope("fleetsim.pack"):
            return pack_state(cfg, st), None

    n_main = n_chunks * k
    with jax.named_scope("fleetsim.pack"):
        packed = pack_state(cfg, state)
    packed, _ = jax.lax.scan(
        chunk, packed,
        (ticks[:n_main].reshape(n_chunks, k),
         n_raw[:n_main].reshape(n_chunks, k)))
    with jax.named_scope("fleetsim.pack"):
        state = unpack_state(packed)
    if n_tail:
        state, _ = jax.lax.scan(step, state,
                                (ticks[n_main:], n_raw[n_main:]))
    return state
