"""Rolling mixed-timestep batch state for the continuous scheduler.

A :class:`RollingBatch` owns one *shape bucket*'s device-resident row
state — the ``(B_cap, ...)``-leading buffers that
``core.sampling.sample_ensemble_step`` advances — plus the host-side
bookkeeping that maps requests onto rows.  The capacity ``B_cap`` is
fixed at construction, so every tick of the bucket feeds the compiled
rolling step the **same shapes** whatever requests join or leave: churn
is ``.at[rows].set`` buffer writes (eager ops, cached by shape), never a
retrace of the step program.

Row lifecycle (the device encoding is ``t_idx``):

* ``t_idx == num_steps`` — free/finished sentinel.  The row is frozen by
  the step program (latent passes through, index does not advance), so a
  partially-full batch costs padded FLOPs but stays bit-exact.
* ``t_idx == 0`` — set at admission together with the request's own
  ``N(0, 1)`` noise (drawn from *its* key, exactly as ``generate``
  would), zeroed routing slots, and its conditioning rows.
* ``0 < t_idx < num_steps`` — in flight; advances by 1 per tick.

Requests occupy ``batch_size`` contiguous-in-order (not necessarily
adjacent) rows; resolution slices those rows back out in sample order,
so the result is bitwise what a dedicated ``generate`` call with the
same key would return (proven in ``tests/test_continuous.py``).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np


# Row-churn device ops, jitted: one compiled dispatch per admission /
# release instead of a chain of eager scatters (eager op dispatch costs
# milliseconds each on the hot scheduler tick; these are the ops a
# profile shows dominating an eager implementation).  jit caches per
# (capacity, batch_size) shape pair — at most ``capacity`` variants.

@jax.jit
def _scatter_admit(x, t_idx, slot_idx, slot_w, idx, noise):
    return (
        x.at[idx].set(noise),
        t_idx.at[idx].set(0),
        slot_idx.at[idx].set(0),
        slot_w.at[idx].set(0.0),
    )


@jax.jit
def _scatter_text(text, idx, emb):
    return text.at[idx].set(emb)


@jax.jit
def _scatter_restore(x, t_idx, slot_idx, slot_w, idx, xv, tv, siv, swv):
    return (
        x.at[idx].set(xv),
        t_idx.at[idx].set(tv),
        slot_idx.at[idx].set(siv),
        slot_w.at[idx].set(swv),
    )


@jax.jit
def _scatter_t(t_idx, idx, value):
    return t_idx.at[idx].set(value)


@jax.jit
def _take_rows(x, idx):
    return x[idx]


@functools.partial(jax.jit, static_argnums=(1,))
def draw_noise(key, shape):
    """Request-key initial noise, bitwise what ``generate`` draws (the
    sampler's own in-jit ``jax.random.normal`` on the same key)."""
    return jax.random.normal(key, shape, jnp.float32)


class RollingBatch:
    """Device row buffers + host row map for one shape bucket.

    ``membership`` is the admission-time elastic snapshot tuple
    ``(epoch, store, tables, cluster_map)`` shared by every request in
    the bucket (the bucket key includes the epoch), or ``None`` on a
    fixed-membership engine.
    """

    def __init__(
        self,
        *,
        capacity: int,
        latent_shape: tuple[int, ...],
        k_slots: int,
        num_steps: int,
        text_tail: tuple[int, ...] | None = None,
        membership: tuple | None = None,
    ) -> None:
        self.capacity = capacity
        self.latent_shape = tuple(latent_shape)
        self.num_steps = num_steps
        self.text_tail = tuple(text_tail) if text_tail is not None else None
        self.membership = membership
        self.x = jnp.zeros((capacity,) + self.latent_shape, jnp.float32)
        self.t_idx = jnp.full((capacity,), num_steps, jnp.int32)
        #: host mirror of ``t_idx``.  Row progress is deterministic —
        #: every active row advances exactly 1 per tick — so completion
        #: detection never has to read the device buffer back: ticks
        #: stay fully asynchronous and the device pipeline never drains
        #: on a scheduler round-trip.  ``advance_host()`` keeps it in
        #: lockstep with the compiled step's ``t_idx + active`` update.
        self.t_host = np.full((capacity,), num_steps, np.int32)
        self.slot_idx = jnp.zeros((capacity, k_slots), jnp.int32)
        self.slot_w = jnp.zeros((capacity, k_slots), jnp.float32)
        self.text = (
            jnp.zeros((capacity,) + self.text_tail, jnp.float32)
            if self.text_tail is not None else None
        )
        #: row -> resident request (or None); requests own their
        #: ``batch_size`` rows from admission to resolution/release.
        self.rows: list = [None] * capacity
        #: request.seq -> ordered row indices (sample order).
        self._rows_of: dict[int, list[int]] = {}
        #: admission order (seq) — resolution and failure handling walk
        #: requests oldest-first so re-queues preserve seq order.
        self._order: list[int] = []
        self._by_seq: dict[int, object] = {}
        #: ``t_idx`` outputs of dispatched ticks the scheduler has not yet
        #: seen finish on the device, oldest first (never donated, so
        #: holding them keeps no latent alive).
        self.unfinished: collections.deque = collections.deque()

    # -- occupancy ----------------------------------------------------------

    def free_count(self) -> int:
        return sum(r is None for r in self.rows)

    @property
    def num_resident(self) -> int:
        return len(self._order)

    def resident_requests(self) -> list:
        """Resident requests, oldest (lowest seq) first."""
        return [self._by_seq[s] for s in sorted(self._order)]

    def rows_of(self, seq: int) -> list[int]:
        """The ordered rows a resident request occupies (sample order)."""
        return list(self._rows_of[seq])

    # -- admission / release ------------------------------------------------

    def admit(self, req, noise: jax.Array) -> list[int]:
        """Place ``req`` into the lowest free rows; returns the rows.

        ``noise`` is the request's own ``(batch_size, *latent)`` initial
        noise.  Buffer writes go through one jitted scatter call (cached
        per batch_size), not a chain of eager ops — eager dispatch is
        the scheduler's dominant host cost otherwise.
        """
        free = [i for i, r in enumerate(self.rows) if r is None]
        if len(free) < req.batch_size:
            raise RuntimeError(
                f"bucket has {len(free)} free rows < batch_size "
                f"{req.batch_size} (admission control should gate this)"
            )
        rows = free[: req.batch_size]
        idx = jnp.asarray(rows, jnp.int32)
        self.x, self.t_idx, self.slot_idx, self.slot_w = _scatter_admit(
            self.x, self.t_idx, self.slot_idx, self.slot_w, idx, noise
        )
        self.t_host[rows] = 0
        if self.text is not None:
            self.text = _scatter_text(
                self.text, idx, jnp.asarray(req.text_emb, jnp.float32)
            )
        for i in rows:
            self.rows[i] = req
        self._rows_of[req.seq] = rows
        self._order.append(req.seq)
        self._by_seq[req.seq] = req
        return rows

    def admit_restored(
        self, req, x, t_idx, slot_idx, slot_w,
    ) -> list[int]:
        """Re-admit a request at a journal-snapshot row state.

        The crash-recovery path (``serving.resilience.RequestJournal``):
        instead of fresh key-derived noise at ``t=0``, the request's rows
        are written back exactly as the snapshot captured them — latent,
        step index, and routing slots — so the compiled step resumes the
        *identical* trajectory (``sample_ensemble_step`` refreshes
        routing on each row's own ``t_idx`` phase; everything else is a
        pure function of this row state).  Conditioning rows re-scatter
        from the request handle as on first admission.
        """
        free = [i for i, r in enumerate(self.rows) if r is None]
        if len(free) < req.batch_size:
            raise RuntimeError(
                f"bucket has {len(free)} free rows < batch_size "
                f"{req.batch_size} (restore admission should gate this)"
            )
        rows = free[: req.batch_size]
        idx = jnp.asarray(rows, jnp.int32)
        t_np = np.asarray(t_idx, np.int32)
        self.x, self.t_idx, self.slot_idx, self.slot_w = _scatter_restore(
            self.x, self.t_idx, self.slot_idx, self.slot_w, idx,
            jnp.asarray(x, jnp.float32), jnp.asarray(t_np),
            jnp.asarray(slot_idx, jnp.int32),
            jnp.asarray(slot_w, jnp.float32),
        )
        self.t_host[rows] = t_np
        if self.text is not None:
            self.text = _scatter_text(
                self.text, idx, jnp.asarray(req.text_emb, jnp.float32)
            )
        for i in rows:
            self.rows[i] = req
        self._rows_of[req.seq] = rows
        self._order.append(req.seq)
        self._by_seq[req.seq] = req
        return rows

    def row_state(self, seq: int) -> dict:
        """Host snapshot of one resident request's row state (the
        journal's latent-snapshot payload).  Materializes the request's
        rows of ``x``/``slot_idx``/``slot_w`` (a device→host read — the
        snapshot cadence pays this, never the per-tick event path) and
        reads ``t`` from the host mirror."""
        rows = self._rows_of[seq]
        idx = jnp.asarray(rows, jnp.int32)
        return {
            "x": np.asarray(_take_rows(self.x, idx)),
            "t": self.t_host[rows].copy(),
            "slot_idx": np.asarray(_take_rows(self.slot_idx, idx)),
            "slot_w": np.asarray(_take_rows(self.slot_w, idx)),
        }

    def release(self, req, *, finished: bool = False) -> list[int]:
        """Free ``req``'s rows (failure path or post-resolution).

        Sets the rows' ``t_idx`` back to the sentinel so an in-flight
        row of a failed request stops advancing immediately.  When the
        request ran to completion (``finished=True``), the compiled step
        already parked those rows at the sentinel — the device write is
        skipped and only host bookkeeping runs.
        """
        rows = self._rows_of.pop(req.seq, [])
        if rows:
            if not finished:
                self.t_idx = _scatter_t(
                    self.t_idx,
                    jnp.asarray(rows, jnp.int32),
                    jnp.int32(self.num_steps),
                )
            self.t_host[rows] = self.num_steps
            for i in rows:
                self.rows[i] = None
        if req.seq in self._order:
            self._order.remove(req.seq)
        self._by_seq.pop(req.seq, None)
        return rows

    # -- completion ---------------------------------------------------------

    def advance_host(self, steps: int = 1) -> None:
        """Mirror one compiled tick on the host counters: every active
        row advances ``steps`` (the tick's ``steps_per_tick``), clamped
        at the sentinel exactly as the step program freezes finished
        rows mid-tick.  Called by the scheduler after each successful
        bucket advance, so completion detection stays a pure host
        computation — no device→host read-back stalls the rolling
        pipeline."""
        active = (self.t_host >= 0) & (self.t_host < self.num_steps)
        self.t_host[active] = np.minimum(
            self.t_host[active] + steps, self.num_steps
        )

    def t_idx_host(self) -> np.ndarray:
        """Device read-back of the per-row step indices.  Debug/test
        hook only (it forces a sync with the in-flight step); scheduling
        decisions run off the ``t_host`` mirror instead."""
        return np.asarray(jax.device_get(self.t_idx))

    def finished_requests(self, t_host: np.ndarray | None = None) -> list:
        """Resident requests whose every row reached the grid end, in
        seq order (deterministic resolution order).  Reads the host
        mirror unless an explicit snapshot is passed."""
        if t_host is None:
            t_host = self.t_host
        done = []
        for seq in sorted(self._order):
            rows = self._rows_of[seq]
            if all(int(t_host[i]) >= self.num_steps for i in rows):
                done.append(self._by_seq[seq])
        return done

    def resolve(self, req) -> jax.Array:
        """Slice the finished request's latents out (sample order) and
        free its rows."""
        rows = self._rows_of[req.seq]
        out = _take_rows(self.x, jnp.asarray(rows, jnp.int32))
        self.release(req, finished=True)
        return out
