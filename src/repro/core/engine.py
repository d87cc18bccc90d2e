"""Static dataflow token engine.

Cycle-accurate, vectorized reproduction of the paper's fabric:

* every arc is a register pair ``(full: bool, value)`` — the 16-bit data
  register + 1-bit status register of paper Fig. 5 (dadoa/bita, ...);
* a node *fires* when all its input arcs are full and all its output arcs
  are empty (static dataflow: one token per arc);
* one engine cycle = every ready node fires simultaneously.  Because a
  producer may only write an arc that was already empty at the start of
  the cycle, an arc sustains one token per two cycles — the same cadence
  as the paper's str/ack handshake;
* environment buses: *input* arcs are strobed with the next token of their
  feed stream as soon as they drain; *const* arcs always present their
  value (paper: input buses that hold data persistently, e.g. the loop
  increment `dadoe`); *output* arcs are drained by the environment every
  cycle, with the last value and a token count recorded.

The firing step is expressed over flat arrays (opcode[N], in_idx[N,3],
out_idx[N,2]) so that one cycle is a single fused vector computation —
this is what the ``dataflow_fire`` Pallas kernel implements on TPU, and on
the FPGA it is the physically-concurrent operator array.

Non-determinism note: ``ndmerge`` resolves same-cycle arrivals with a
fixed priority (input ``a`` wins).  See DESIGN.md §2.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.alu import (ALU_OPS, _alu_numpy, alu, inputs_ready,
                            truthy)
from repro.core.graph import Graph, Op
from repro.core.plan import MAX_IN, MAX_OUT, plan
from repro.kernels.dataflow_fire import (LANES, FabricSpec, block_plan_arrays,
                                         fire_block_masked_ref, from_tiles,
                                         make_block_step)
from repro.kernels.feed_stage import place_feeds


def _prof_zeros(n_nodes: int, n_arcs: int, batch: int | None = None):
    """Fresh profile accumulators (nf, si, so, ab, ahw) — int32 device
    arrays; node axis may include the pallas tables' dummy row."""
    shp = (batch,) if batch is not None else ()
    z = lambda n: jnp.zeros((*shp, n), jnp.int32)
    return (z(n_nodes), z(n_nodes), z(n_nodes), z(n_arcs), z(n_arcs))


@dataclasses.dataclass
class EngineResult:
    outputs: dict       # arc -> last token value (jnp array)
    counts: dict        # arc -> number of tokens drained
    cycles: int
    fired: int          # total node firings
    dispatches: int | None = None   # device dispatches used (if tracked)
    node_fires: np.ndarray | None = None  # int64[N] per-node firings in
                                          # graph order (profile=on; sums
                                          # exactly to `fired`)
    profile: object | None = None   # FabricProfile (profile=on)


@dataclasses.dataclass
class SlotState:
    """Resumable state of B fabric *slots* (continuous batching).

    A slot is one stream's worth of arc registers, feed pointers, and
    output accumulators riding the shared fabric.  Unlike
    :meth:`DataflowEngine.run_batch` (wave batching: all B streams start
    and finish together), slots have independent lifecycles: a quiesced
    slot can be harvested and refilled with a new request's feed stream
    while the other slots keep running — see
    :class:`repro.serve.dataflow_server.DataflowServer`.

    Device arrays (jnp, int32; leading axis = B slots):
      fv[B, n_in, L], fl[B, n_in]   packed feed streams (L grows on
                                    demand, power-of-two, to bound
                                    recompiles)
      full/val[B, A2]               arc registers
      ptr[B, n_in]                  per-arc feed pointers
      out_last/out_count[B, n_out]  output-bus accumulators

    Host arrays (numpy; the per-slot clock):
      active[B]     1 while a request occupies the slot (gates the
                    kernel's feed/fire/drain — inactive slots are
                    skipped, not stepped)
      base[B]       slot-local cycles simulated so far
      last[B]       slot-local cycle of last progress
      fired[B]      node firings of the resident request
      quiesced[B]   latest block had an idle tail (idle is absorbing,
                    so the resident request is finished)
      dispatches[B] block dispatches the resident request has ridden
      cap[B]        per-slot cycle cap (engine max_cycles unless the
                    admission overrode it via ``reset_slots(caps=)``) —
                    the budget a scheduler shortens blocks against and
                    ``harvest`` clamps the cycle count to
      stalled[B]    consecutive blocks with zero progress (no feed, no
                    firing, no drain) while the slot stayed active —
                    the progress counter a wedged-slot watchdog reads;
                    reset to 0 by any progress and on (re)admission

    Profiling (engine profile=on only; None otherwise):
      prof          tuple of 5 device counter arrays (node_fires,
                    stall_in, stall_out, arc_busy, arc_hw — leading B
                    axis, plan order) accumulated IN-KERNEL alongside
                    the block step, so profiling adds no extra
                    dispatches per block
      prof_cycles[B] host tally of cycles the resident request's slot
                    was simulated for (its profiled-cycle denominator)
    """
    fv: object
    fl: object
    full: object
    val: object
    ptr: object
    out_last: object
    out_count: object
    active: np.ndarray
    base: np.ndarray
    last: np.ndarray
    fired: np.ndarray
    quiesced: np.ndarray
    dispatches: np.ndarray
    cap: np.ndarray = None
    stalled: np.ndarray = None
    active_dev: object = None   # device mirror of `active` (refreshed on
                                # admission/harvest, not per block)
    prof: tuple | None = None
    prof_cycles: np.ndarray = None
    sched: object = None        # scheduled engines: repro.core.schedule
                                # .SlotSched (per-slot plan refs +
                                # schedule positions + host-side §12
                                # counters); None on dynamic engines
    mf: object = None           # partitioned engines: dict with the
                                # replicated channel registers (chf/chv,
                                # [P,B,C]) and channel counters; device
                                # arrays then carry a leading P regions
                                # axis (see core/multifabric.py)

    @property
    def slots(self) -> int:
        return int(self.active.shape[0])

    def free_slots(self) -> list[int]:
        return np.flatnonzero(self.active == 0).tolist()

    def quiesced_slots(self) -> list[int]:
        return np.flatnonzero((self.active != 0) & self.quiesced).tolist()


def feed_capacity(n_in: int, L: int) -> int:
    """Tokens one admission dispatch stages: the streams of eight slots
    at the feed buffer's full length, rounded up to a power of two.  It
    changes only when the feed buffer grows; a round that admits more
    tokens is split over several dispatches of this one shape."""
    return 1 << (8 * n_in * L - 1).bit_length()


def _dispatch_groups(tokens, capacity: int):
    """[a, b) ranges of consecutive admitted slots whose ``tokens`` fit
    ``capacity`` each: one range unless the round overflows."""
    ends = np.cumsum(tokens)
    out, a, base = [], 0, 0
    while a < len(ends):
        b = int(np.searchsorted(ends, base + capacity, side="right"))
        out.append((a, b))
        a, base = b, int(ends[b - 1])
    return out


_NO_TOKENS = np.zeros((0,), np.int32)     # an input arc a request leaves out


def _staged_size(B: int, n_in: int, L: int) -> int:
    """int32 words of one dispatch's staged buffer (see _slot_reset)."""
    return feed_capacity(n_in, L) + B * (n_in + 3)


def _stage(B: int, L: int, active, slots, lens, streams) -> np.ndarray:
    """One dispatch's host buffer (layout: :func:`_slot_reset`): the
    ascending ``slots``' ``streams`` (``lens[len(slots), n_in]``)."""
    n_in = lens.shape[1]
    C = feed_capacity(n_in, L)
    buf = np.zeros((_staged_size(B, n_in, L),), np.int32)
    if streams:
        np.concatenate(streams, out=buf[:int(lens.sum())], casting="unsafe")
    buf[C:C + B * n_in].reshape(B, n_in)[slots] = lens
    mask, order, act = buf[C + B * n_in:].reshape(3, B)
    mask[slots] = 1
    order[:len(slots)] = slots
    act[:] = active
    return buf


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6))
def _slot_reset(fv, fl, full, val, ptr, out_last, out_count, staged,
                full0, val0):
    """Reset the masked slots to fresh initial state + new feed streams
    in ONE fused dispatch (an admission round, not one call per slot).

    staged: the dispatch's one host buffer, int32 words laid out as the
    admitted streams back to back (``feed_capacity`` words, zero
    padded) | fl[B, n_in] | mask[B] | order[B] (admitted slots,
    ascending, first) | active[B].  Returns the reset state, the mask
    and active as device arrays."""
    B, n_in, _ = fv.shape
    C = staged.shape[0] - B * (n_in + 3)
    fl_rows = staged[C:C + B * n_in].reshape(B, n_in)
    mask, order, active = staged[C + B * n_in:].reshape(3, B)
    mask = mask > 0
    m1 = mask[:, None]

    def fresh(x, x0):
        if x.ndim == 2:                 # [B, A2], plan order
            return jnp.where(m1, x0[None], x)
        T, _, s, lanes = x.shape        # the row kernel's [T, R, s, 128]
        mt = jnp.pad(mask, (0, T * s * lanes - B)).reshape(T, 1, s, lanes)
        return jnp.where(mt, x0[None, :, None, None], x)
    return (place_feeds(fv, staged[:C], mask, order, fl_rows),
            jnp.where(m1, fl_rows, fl),
            fresh(full, full0), fresh(val, val0),
            jnp.where(m1, 0, ptr),
            jnp.where(m1, 0, out_last),
            jnp.where(m1, 0, out_count), mask, active)


@functools.partial(jax.jit, donate_argnums=(0,))
def _prof_reset(prof, mask):
    """Zero the masked slots' profile counters (one fused dispatch per
    admission round; only exists on profiled engines — kept out of
    :func:`_slot_reset` so the unprofiled path's dispatch signature and
    count are untouched)."""
    return tuple(
        jnp.where(mask.reshape((-1,) + (1,) * (x.ndim - 1)), 0, x)
        for x in prof)


def pack_feeds(input_arcs, feeds, token_shape=(), dtype=np.int32,
               pad_rows: int | None = None, min_len: int = 1):
    """Dense (feed_vals[n_in, L, *ts], feed_len[n_in]) from an arc->stream
    mapping.  Shared by every backend and by compile_cyclic.  pad_rows
    forces at least that many stream rows (the Pallas block kernel wants
    n_in >= 1); min_len floors L (so a stream axis always exists)."""
    feeds = dict(feeds or {})
    unknown = set(feeds) - set(input_arcs)
    if unknown:
        raise ValueError(f"feeds for non-input arcs: {sorted(unknown)}")
    ts = tuple(token_shape)
    n_in = max(len(input_arcs), pad_rows or 0)
    max_len = max((np.shape(v)[0] for v in feeds.values()), default=0)
    max_len = max(max_len, min_len)
    feed_vals = np.zeros((n_in, max_len, *ts), dtype)
    feed_len = np.zeros((n_in,), np.int32)
    for k, a in enumerate(input_arcs):
        if a in feeds:
            v = np.asarray(feeds[a], dtype)
            if v.shape[1:] != ts:
                v = np.broadcast_to(
                    v.reshape(v.shape[0], *([1] * len(ts))),
                    (v.shape[0], *ts)).astype(dtype)
            feed_vals[k, :v.shape[0]] = v
            feed_len[k] = v.shape[0]
    return feed_vals, feed_len


BACKENDS = ("xla", "pallas", "reference")


def _spans_closed_on_error(method):
    """A slot API method that raises closes the spans it opened on its
    caller's ``obs`` probe; without one it is called as it is."""
    @functools.wraps(method)
    def call(self, *args, obs=None, **kwargs):
        if obs is None:
            return method(self, *args, **kwargs)
        mark = obs.mark()
        try:
            return method(self, *args, obs=obs, **kwargs)
        except BaseException:
            obs.unwind(mark)
            raise
    return call


class DataflowEngine:
    """Cycle-accurate executor for a static dataflow :class:`Graph`.

    backend:
      * ``"xla"``       — vectorized jnp cycle body, ``lax.while_loop``
        over *blocks* of ``block_cycles`` fused cycles (one XLA dispatch
        per run).  Supports tensor tokens and any dtype.  Batched runs
        vmap the whole block loop.
      * ``"pallas"``    — the row kernel (``dataflow_fire_block``): K
        cycles + environment feed/drain per device dispatch, the
        fabric's tables in SMEM and a slot tile's registers in VMEM
        within a block.  Scalar int32 tokens.  ``run()``/``run_batch()``
        are one wave of the slot API (:meth:`_wave`): every stream
        admitted at once, one dispatch per block for all of them.
      * ``"reference"`` — the pure-numpy oracle (`run_reference`).

    All backends share one :func:`repro.core.plan.plan` arc/state layout
    and report bit-identical outputs/counts/fired; ``cycles`` is
    reconstructed from the last progress cycle, so block-granular
    quiescence detection does not change the reported cycle count.
    """

    def __init__(self, graph: Graph, token_shape: tuple[int, ...] = (),
                 dtype=jnp.int32, max_cycles: int = 100_000,
                 backend: str = "xla", block_cycles: int = 1,
                 optimize: bool = False, profile: bool = False,
                 schedule: bool | str = False, partition=None):
        if backend not in BACKENDS:
            raise ValueError(f"backend {backend!r} not in {BACKENDS}")
        if block_cycles < 1:
            raise ValueError("block_cycles must be >= 1")
        self.graph = graph
        self.token_shape = tuple(token_shape)
        self.dtype = jnp.dtype(dtype)
        self.max_cycles = max_cycles
        self.backend = backend
        self.block_cycles = int(block_cycles)
        # optimize=True builds the opcode-class-specialized plan
        # (DESIGN.md §8): permuted node/arc tables + bucketed fire
        # bodies.  Pure layout change — results stay bit-identical.
        # (The reference backend is the oracle and always runs the
        # graph as authored.)
        self.optimize = bool(optimize)
        # profile=True accumulates the DESIGN.md §12 fabric counters in
        # device state alongside every run/block step.  Results stay
        # bit-identical; with profile=False the traced computations are
        # byte-for-byte the pre-observability ones (zero overhead, zero
        # extra dispatches).
        self.profile = bool(profile)
        # schedule: False/None = dynamic interpreter; "auto" = compile
        # the static firing schedule when the fabric is control-free
        # (DESIGN.md §13), dynamic otherwise; True = require the
        # schedule (raise naming the blockers if the fabric can't be
        # scheduled).  Scheduled execution stays bit-identical to the
        # dynamic engine in every reported field; a plan that fails to
        # lock onto a period in budget silently falls back to the
        # dynamic run path (a perf decision, never a semantic one).
        if schedule not in (False, None, True, "auto"):
            raise ValueError("schedule must be False, True, or 'auto', "
                             f"got {schedule!r}")
        self.schedule = schedule
        self._sched = None
        self._sched_on = False
        self.sched_bails = 0    # run()/run_batch() calls that fell back
        if schedule:
            from repro.core.schedule import schedule_blockers
            blockers = schedule_blockers(graph)
            if blockers and schedule is True:
                raise ValueError(
                    "schedule=True needs a statically schedulable "
                    f"fabric, but this one has: {', '.join(blockers)} "
                    "(use schedule='auto' to fall back dynamically)")
            self._sched_on = not blockers
        # partition: None/1 = solo fabric; int P / "auto" / Partition =
        # shard the graph into P regions (DESIGN.md §14) and run them as
        # communicating fabrics under shard_map (or a vmap'd shards axis
        # on a single device).  Every run/slot entry point delegates to
        # core/multifabric.py when engaged; results stay bit-identical
        # to the solo fabric in every field.
        self.partition = None
        self._mf = None
        if partition is not None:
            from repro.core.partition import resolve_partition
            self.partition = resolve_partition(graph, partition)
        self._part_on = (self.partition is not None
                         and self.partition.P > 1)
        if self._part_on:
            if backend == "reference":
                raise ValueError(
                    "partitioned execution needs a device backend "
                    "(xla or pallas), not 'reference' — the reference "
                    "oracle IS the solo fabric the shards are checked "
                    "against")
            if self.token_shape != ():
                raise ValueError(
                    "partitioned execution supports scalar tokens only")
            if schedule is True:
                raise ValueError(
                    "schedule=True cannot compose with partition > 1 "
                    "(regions run the dynamic cycle body; use "
                    "schedule='auto' to let partition win)")
            # regions execute the fused SPMD cycle body; the static
            # firing schedule is a whole-fabric single-device program
            self._sched_on = False
        self.p = plan(graph, optimize=self.optimize)
        self._slot_steps: dict[int, object] = {}
        self._state0_dev = None     # (full0, val0) on the device, once
        self._tables = None
        self._spec = None           # the pallas kernels' FabricSpec
        if self._part_on:
            pass    # multifabric builds its own per-region tables lazily
        elif backend == "pallas":
            if self.token_shape != () or self.dtype != jnp.int32:
                raise ValueError(
                    "pallas backend supports scalar int32 tokens only")
            self._tables = self._block_tables()
        else:
            self._run = jax.jit(self._run_impl,
                                static_argnames=("max_cycles",))
            self._vruns: dict[int, object] = {}

    def _mf_ctx(self):
        """Lazy per-engine multi-fabric runtime (DESIGN.md §14)."""
        if self._mf is None:
            from repro.core.multifabric import MultiFabric
            self._mf = MultiFabric(
                self.graph, self.partition, dtype=self.dtype,
                block_cycles=self.block_cycles, optimize=self.optimize,
                profile=self.profile, max_cycles=self.max_cycles)
        return self._mf

    def _block_tables(self):
        """Gather-layout node/arc/environment tables (built lazily for
        the xla backend, eagerly for pallas)."""
        if self._tables is None:
            self._tables = block_plan_arrays(self.graph,
                                             optimize=self.optimize)
        return self._tables

    def _fabric_spec(self):
        """The pallas kernels' static view of the tables, built once."""
        if self._spec is None:
            self._spec = FabricSpec(self._block_tables())
        return self._spec

    def kernel_choice(self, slots: int) -> str:
        """The device kernel a slot step of ``slots`` slots runs and its
        tiling: ``"rows TxS"`` (the row kernel over T slot tiles of S
        sublanes), else the backend's own path."""
        tiles = self._row_tiles(slots)
        if tiles is not None:
            return "rows {}x{}".format(*tiles)
        if self._part_on:
            return "partitioned"
        return "scheduled" if self._sched_on else self.backend

    def _row_tiles(self, slots: int):
        """(T, s) where a slot step of ``slots`` slots runs the row kernel
        (its register file is then kept in the kernel's tile layout,
        ``SlotState.full``/``val`` [T, R, s, 128] in row order), else
        None (plan order, [B, A2])."""
        if self.backend != "pallas" or self._part_on or self._sched_on:
            return None
        return self._fabric_spec().rows.tiles(slots, self.block_cycles,
                                              self.profile)

    def slot_registers(self, state: SlotState):
        """(full, val) [B, A2] of a slot state in the plan's arc order,
        whatever layout the state keeps them in."""
        if state.full.ndim == 2:
            return state.full, state.val
        lay = self._fabric_spec().rows
        return tuple(from_tiles(x.reshape(x.shape[0], -1, LANES),
                                state.slots, lay.R, lay.dst)
                     for x in (state.full, state.val))

    def _slot_rows0(self, tiled: bool):
        """(full0, val0) of a fresh slot in the slot state's order."""
        full0, val0 = self._state0_rows()
        if tiled:
            src = self._fabric_spec().rows.src
            full0, val0 = full0[src], val0[src]
        return full0, val0

    def _sched_ctx(self):
        """Lazy per-engine schedule state (DESIGN.md §13)."""
        if self._sched is None:
            from repro.core.schedule import ScheduleContext
            self._sched = ScheduleContext(self.p, self.graph,
                                          self.token_shape, self.dtype)
        return self._sched

    # -- public ---------------------------------------------------------
    def run(self, feeds: Mapping[str, object] | None = None,
            max_cycles: int | None = None) -> EngineResult:
        """feeds: arc -> [k, *token_shape] stream of tokens (k may vary)."""
        max_cycles = max_cycles or self.max_cycles
        if self._part_on:
            return self._mf_ctx().run(feeds, max_cycles)
        if self._sched_on:
            from repro.core import schedule as _sched
            try:
                return _sched.run_scheduled(self, feeds, max_cycles)
            except _sched.ScheduleBail:
                # pathological period: the dynamic path below runs it
                self.sched_bails += 1
        if self.backend == "reference":
            return run_reference(self.graph, feeds, self.token_shape,
                                 np.dtype(str(self.dtype)), max_cycles,
                                 profile=self.profile)
        if self.backend == "pallas":
            return self._wave([feeds], max_cycles)[0]
        p = self.p
        feed_vals, feed_len = pack_feeds(
            p["input_arcs"], feeds, self.token_shape, self.dtype)
        res = self._run(jnp.asarray(feed_vals), jnp.asarray(feed_len),
                        max_cycles=max_cycles)
        outs, counts, cycles, fired = res[:4]
        prof = None
        if self.profile:
            prof = (*jax.device_get(res[4:9]), int(res[9]), 1)
        return self._result_from_state(outs, counts, int(cycles),
                                       int(fired), dispatches=1,
                                       prof=prof)

    def run_batch(self, feeds_batch, max_cycles: int | None = None
                  ) -> list[EngineResult]:
        """Execute B independent token streams through one fabric.

        feeds_batch: sequence of B feed dicts (streams may have unequal
        lengths — shorter streams quiesce early and idle harmlessly).
        Returns one EngineResult per stream, bit-identical to running
        each stream alone."""
        max_cycles = max_cycles or self.max_cycles
        feeds_batch = list(feeds_batch)
        if not feeds_batch:
            raise ValueError(
                "run_batch: feeds_batch is empty — pass at least one "
                "feed dict (use run() for a single stream)")
        if self._part_on:
            return self._mf_ctx().run_batch(feeds_batch, max_cycles)
        if self._sched_on:
            from repro.core import schedule as _sched
            try:
                res = _sched.run_batch_scheduled(self, feeds_batch,
                                                 max_cycles)
            except _sched.ScheduleBail:
                self.sched_bails += 1
                res = None
            if res is not None:     # None: mixed feed lengths — the
                return res          # schedule is per-length; dynamic
                                    # path handles the ragged batch
        if self.backend == "reference":
            return [run_reference(self.graph, f, self.token_shape,
                                  np.dtype(str(self.dtype)), max_cycles,
                                  profile=self.profile)
                    for f in feeds_batch]
        if self.backend == "pallas":
            return self._wave(feeds_batch, max_cycles)
        L = max((max((np.shape(v)[0] for v in (f or {}).values()),
                     default=0) for f in feeds_batch), default=0)
        packed = [pack_feeds(self.p["input_arcs"], f, self.token_shape,
                             self.dtype, min_len=max(L, 1))
                  for f in feeds_batch]
        feed_vals = np.stack([fv for fv, _ in packed])
        feed_len = np.stack([fl for _, fl in packed])
        vrun = self._vruns.get(max_cycles)
        if vrun is None:
            mc = max_cycles
            vrun = jax.jit(jax.vmap(
                lambda fv, fl: self._run_impl(fv, fl, max_cycles=mc)))
            self._vruns[max_cycles] = vrun
        res = vrun(jnp.asarray(feed_vals), jnp.asarray(feed_len))
        outs, counts, cycles, fired = res[:4]
        prof = jax.device_get(res[4:10]) if self.profile else None
        return [self._result_from_state(
            outs[b], counts[b], int(cycles[b]), int(fired[b]), dispatches=1,
            prof=None if prof is None else
            (*(x[b] for x in prof[:5]), int(prof[5][b]), 1))
            for b in range(len(feeds_batch))]

    def _result_from_state(self, out_last, out_count, cycles, fired,
                           dispatches, prof=None):
        """Per-arc result dicts from flat accumulators (all backends).

        prof: optional (nf, si, so, ab, ahw, profiled_cycles,
        dispatches) plan-order counter tuple — converted to a
        graph-order :class:`repro.obs.FabricProfile`."""
        out_arcs = self.p["output_arcs"]
        profile = node_fires = None
        if prof is not None:
            from repro.obs.profile import FabricProfile
            profile = FabricProfile.from_plan(self.graph, self.p,
                                              *prof[:5], cycles=prof[5],
                                              dispatches=prof[6])
            node_fires = profile.node_fires
        return EngineResult(
            outputs={a: out_last[i] for i, a in enumerate(out_arcs)},
            counts={a: int(out_count[i]) for i, a in enumerate(out_arcs)},
            cycles=cycles, fired=fired, dispatches=dispatches,
            node_fires=node_fires, profile=profile)

    # -- resumable slot API (continuous batching) ------------------------
    #
    # Lifecycle: init_state(B) -> all slots free; reset_slots() admits
    # requests into free slots; step_block() advances every *active*
    # slot by exactly block_cycles fabric cycles in one dispatch
    # (inactive slots are clock-gated out of feed/fire/drain);
    # harvest() extracts finished results and frees the slots.  Because
    # admissions happen only at block boundaries and each slot carries
    # its own cycle clock, a request's result is bit-identical to
    # running it alone via run() — see DESIGN.md §7.
    def _check_slot_api(self):
        if self.backend == "reference":
            raise ValueError("the resumable slot API needs a device "
                             "backend (xla or pallas), not 'reference'")
        if self.token_shape != () or self.dtype != jnp.int32:
            raise ValueError("the resumable slot API supports scalar "
                             "int32 tokens only")

    def _state0_rows(self):
        """(full0[A2], val0[A2]) int32 rows of a freshly-reset slot."""
        p = self.p
        full = np.zeros((p["A"] + 2,), np.int32)
        val = np.zeros((p["A"] + 2,), np.int32)
        full[p["FULL_PAD"]] = 1
        for a, v in self.graph.consts.items():
            full[p["aidx"][a]] = 1
            val[p["aidx"][a]] = int(v)
        for a, v in self.graph.inits.items():    # one-shot initial tokens
            full[p["aidx"][a]] = 1
            val[p["aidx"][a]] = int(v)
        return full, val

    def init_state(self, slots: int) -> SlotState:
        """Fresh B-slot state, every slot free (active == 0)."""
        return self._init_state(slots, self._sched_on)

    def _init_state(self, slots: int, scheduled: bool) -> SlotState:
        """init_state; ``scheduled=False`` gives a scheduled engine a
        dynamic state, which the slot API steps with the dynamic kernel
        (the wave's fallback when the schedule cannot run)."""
        self._check_slot_api()
        if slots < 1:
            raise ValueError("slots must be >= 1")
        if self._part_on:
            return self._mf_ctx().slot_init(int(slots))
        p = self.p
        B = int(slots)
        n_in = max(len(p["input_arcs"]), 1)
        n_out = max(len(p["output_arcs"]), 1)
        tiles = self._row_tiles(B)
        full0, val0 = self._slot_rows0(tiles is not None)
        if tiles is None:
            regs = lambda x0: jnp.asarray(
                np.broadcast_to(x0, (B, x0.shape[0])).copy())
        else:
            T, sub = tiles
            regs = lambda x0: jnp.broadcast_to(
                jnp.asarray(x0)[None, :, None, None],
                (T, x0.shape[0], sub, 128))
        z64 = lambda: np.zeros((B,), np.int64)
        return SlotState(
            fv=jnp.zeros((B, n_in, 1), jnp.int32),
            fl=jnp.zeros((B, n_in), jnp.int32),
            full=regs(full0), val=regs(val0),
            ptr=jnp.zeros((B, n_in), jnp.int32),
            out_last=jnp.zeros((B, n_out), jnp.int32),
            out_count=jnp.zeros((B, n_out), jnp.int32),
            active=np.zeros((B,), np.int32), base=z64(), last=z64(),
            fired=z64(), quiesced=np.zeros((B,), bool), dispatches=z64(),
            cap=np.full((B,), self.max_cycles, np.int64), stalled=z64(),
            active_dev=jnp.zeros((B,), jnp.int32),
            # profiled engines ride the counters in device state; the
            # slot steppers run on the kernel tables (N+1 node rows).
            # Scheduled engines reconstruct profiles on the host from
            # the plan instead (closed form — no device counters).
            prof=_prof_zeros(len(self.graph.nodes) + 1, p["A"] + 2,
                             batch=B)
            if self.profile and not scheduled else None,
            prof_cycles=z64() if self.profile else None,
            sched=self._make_slot_sched(B) if scheduled else None)

    def _make_slot_sched(self, slots: int):
        from repro.core.schedule import SlotSched
        return SlotSched(self._sched_ctx(), slots, self.profile)

    def _slot_step(self, n_cycles: int):
        """Jitted masked batched block step of the dynamic fabric, one
        per block length, compiled lazily: the row kernel on pallas, the
        vmapped jnp block on xla."""
        step = self._slot_steps.get(n_cycles)
        if step is None:
            if self.backend == "pallas":
                step = make_block_step(self._fabric_spec(), n_cycles,
                                       self.profile)
            else:
                vstep = jax.vmap(functools.partial(
                    fire_block_masked_ref, self._block_tables(),
                    n_cycles=n_cycles))

                def dataflow_slot_step_xla(*args):   # the module's name
                    return vstep(*args)
                step = jax.jit(dataflow_slot_step_xla)
            self._slot_steps[n_cycles] = step
        return step

    @_spans_closed_on_error
    def reset_slots(self, state: SlotState, slot_ids,
                    new_feeds, caps=None, obs=None) -> SlotState:
        """Admit one request per slot id: fresh arc registers + the new
        feed stream, in one fused dispatch for the whole round.  Slots
        must be free (never-used or harvested); everything else keeps
        its state untouched.

        Ragged staging: only the admitted tokens travel, packed back to
        back into one host buffer of ``feed_capacity`` words per
        dispatch, with the per-slot lengths beside them; the device
        writes the admitted rows of the feed buffer from it
        (``repro.kernels.feed_stage``).  A round with more tokens than
        the capacity is split over several dispatches of the same shape.

        caps: optional per-admission cycle caps (one entry per slot id;
        ``None`` entries fall back to the engine's ``max_cycles``) — a
        request-level budget the scheduler enforces by shortening
        blocks and ``harvest`` clamps cycle accounting to.

        MOVE semantics: the input state's device buffers are donated to
        the fused reset dispatch, so ``state`` (and any older SlotState
        sharing its buffers) must not be used again on backends that
        honor donation — always continue from the returned state.

        obs: the calling server's :class:`repro.obs.Probe` (or None): on
        the dynamic path it times packing, the copies to the device and
        the reset dispatch as spans, and counts the bytes copied and the
        extra dispatches an overflowing round took (``admit_splits``)."""
        self._check_slot_api()
        if self._part_on:
            return self._mf_ctx().slot_reset(state, slot_ids, new_feeds,
                                             caps)
        slot_ids = list(slot_ids)
        new_feeds = list(new_feeds)
        if len(slot_ids) != len(new_feeds):
            raise ValueError(f"{len(slot_ids)} slot ids but "
                             f"{len(new_feeds)} feed dicts")
        if not slot_ids:
            return state
        busy = [b for b in slot_ids if state.active[b]]
        if busy:
            raise ValueError(f"slots {busy} still hold unharvested "
                             "requests (harvest before refilling)")
        if caps is None:
            caps = [None] * len(slot_ids)
        if len(caps) != len(slot_ids):
            raise ValueError(f"{len(slot_ids)} slot ids but "
                             f"{len(caps)} caps")
        cap = state.cap.copy()
        for b, c in zip(slot_ids, caps):
            if c is not None and int(c) < 1:
                raise ValueError(f"slot {b}: cap must be >= 1, got {c}")
            cap[b] = self.max_cycles if c is None else int(c)
        p = self.p
        B = state.slots
        if obs is not None:
            sp = obs.begin("dataflow.admit.pack")
        rows = sorted(range(len(slot_ids)), key=slot_ids.__getitem__)
        slots = [slot_ids[i] for i in rows]
        streams, lens = self._admitted_streams([new_feeds[i] for i in rows])
        n_in = lens.shape[1]
        L = state.fv.shape[2]
        need = int(lens.max(initial=1))
        if need > L:        # grow the stream buffer (pow2 bounds retraces)
            L = 1 << (need - 1).bit_length()
            grow = ((0, 0), (0, 0), (0, L - state.fv.shape[2]))
            state = dataclasses.replace(state, fv=jnp.pad(state.fv, grow))
            if obs is not None:
                obs.count("retraces", what="feed_buffer")
        active = state.active.copy()
        active[slot_ids] = 1
        staged = [_stage(B, L, active, slots[a:b], lens[a:b],
                         streams[a * n_in:b * n_in])
                  for a, b in _dispatch_groups(lens.sum(axis=1),
                                               feed_capacity(n_in, L))]
        if self._state0_dev is None:
            self._state0_dev = tuple(jnp.asarray(x) for x in
                                     self._slot_rows0(state.full.ndim == 4))
        if obs is not None:
            obs.end(sp)
            nbytes = sum(x.nbytes for x in staged)
            obs.count("h2d_bytes", nbytes, site="admit")
            if len(staged) > 1:
                obs.count("admit_splits", len(staged) - 1)
            sp = obs.begin("dataflow.admit.h2d", bytes=nbytes)
        staged = [jnp.asarray(x) for x in staged]
        if obs is not None:
            obs.end(sp)
            sp = obs.begin("dataflow.admit.dispatch")
        dev = (state.fv, state.fl, state.full, state.val, state.ptr,
               state.out_last, state.out_count)
        prof, prof_cycles = state.prof, state.prof_cycles
        for x in staged:
            *dev, mask_d, active_d = _slot_reset(*dev, x, *self._state0_dev)
            if self.profile and prof is not None:
                prof = _prof_reset(prof, mask_d)
        for host in (base := state.base.copy(), last := state.last.copy(),
                     fired := state.fired.copy(),
                     disp := state.dispatches.copy(),
                     stalled := state.stalled.copy()):
            host[slot_ids] = 0
        quiesced = state.quiesced.copy()
        quiesced[slot_ids] = False
        if self.profile:
            prof_cycles = prof_cycles.copy()
            prof_cycles[slot_ids] = 0
        sched = state.sched
        if sched is not None:
            ctx = self._sched_ctx()
            n_real = len(p["input_arcs"])
            for b, fl in zip(slots, lens):
                sched.reset(b, ctx.plan_for(tuple(int(x)
                                                  for x in fl[:n_real])))
        if obs is not None:
            obs.end(sp)
        return SlotState(*dev, active, base, last, fired, quiesced, disp,
                         cap=cap, stalled=stalled,
                         active_dev=active_d,
                         prof=prof, prof_cycles=prof_cycles,
                         sched=sched)

    def _admitted_streams(self, feeds_list):
        """(streams, lens[R, n_in]): every admitted request's input-arc
        streams in plan order, a missing arc (and the pad row of a
        fabric without inputs) as an empty stream, R requests in
        order.  Raises like :func:`pack_feeds` on a non-input arc."""
        arcs = self.p["input_arcs"]
        known = set(arcs)
        pad = [_NO_TOKENS] * (max(len(arcs), 1) - len(arcs))
        streams = []
        for feeds in feeds_list:
            feeds = feeds or {}
            if not known.issuperset(feeds):
                raise ValueError("feeds for non-input arcs: "
                                 f"{sorted(set(feeds) - known)}")
            streams += [feeds.get(a, _NO_TOKENS) for a in arcs]
            streams += pad
        lens = np.fromiter(map(len, streams), np.int64, len(streams))
        return streams, lens.reshape(len(feeds_list), -1)

    @_spans_closed_on_error
    def step_block(self, state: SlotState,
                   n_cycles: int | None = None, obs=None) -> SlotState:
        """Advance every active slot by ``n_cycles`` (default
        ``block_cycles``) fabric cycles in ONE device dispatch; free
        slots are clock-gated out.  Per-slot clocks (base/last/fired)
        advance on the host; a slot whose block had an idle tail is
        marked ``quiesced`` (idle is absorbing — the request is done).

        obs: the calling server's :class:`repro.obs.Probe` (or None): on
        the dynamic path it splits enqueueing the step from waiting for
        its readback, spans a step's first compile for its shapes
        (``dataflow.build``), and counts slot-cycles, node firings,
        readback bytes and new jitted steps."""
        self._check_slot_api()
        nb = self.block_cycles if n_cycles is None else int(n_cycles)
        if nb < 1:
            raise ValueError("n_cycles must be >= 1")
        if not state.active.any():
            return state
        if self._part_on:
            return self._mf_ctx().slot_step(state, nb)
        if state.sched is not None:
            from repro.core import schedule as _sched
            return _sched.step_block_sched(self, state, nb)
        build = -1
        if obs is not None:
            sp = obs.begin("dataflow.step.dispatch")
            n_steps = len(self._slot_steps)
            if nb not in self._slot_steps:  # lowered and compiled now
                build = obs.begin("dataflow.build",
                                  nodes=len(self.graph.nodes),
                                  arcs=self.p["A"],
                                  kernel=self.kernel_choice(state.slots))
        step = self._slot_step(nb)
        active_dev = state.active_dev if state.active_dev is not None \
            else jnp.asarray(state.active)
        if self.profile:
            res = step(state.fv, state.fl, state.full, state.val,
                       state.ptr, state.out_last, state.out_count,
                       active_dev, *state.prof)
            dev, f, lp, prof = res[:5], res[5], res[6], tuple(res[7:12])
        else:
            *dev, f, lp = step(state.fv, state.fl, state.full, state.val,
                               state.ptr, state.out_last, state.out_count,
                               active_dev)
            prof = state.prof
        if obs is not None:
            obs.end(build)
            if len(self._slot_steps) > n_steps:
                obs.count("retraces", what="step")
            obs.end(sp)
            obs.count("d2h_bytes", f.nbytes + lp.nbytes, site="step")
            obs.count("slot_cycles", state.slots * nb)
            obs.count("active_slot_cycles", int(state.active.sum()) * nb)
            sp = obs.begin("dataflow.step.wait")
        f, lp = jax.device_get((f, lp))      # one host sync per block
        f = np.asarray(f).reshape(state.slots)
        if obs is not None:
            obs.end(sp)
            obs.count("node_firings", int(f.sum()))
        lp = np.asarray(lp).reshape(state.slots)
        fired = state.fired + f
        last = np.where(lp > 0, state.base + lp, state.last)
        base = state.base + np.where(state.active > 0, nb, 0)
        quiesced = np.where(state.active > 0, lp < nb, state.quiesced)
        disp = state.dispatches + (state.active > 0)
        # progress counter: an active slot whose whole block was idle
        # stalls by one more block; any progress resets it.  A healthy
        # idle slot is harvested as quiesced the same heartbeat, so a
        # *growing* stall count means the quiescence signal is being
        # withheld — the watchdog's trigger (DESIGN.md §11).
        stalled = np.where(state.active > 0,
                           np.where(lp > 0, 0, state.stalled + 1),
                           state.stalled)
        prof_cycles = state.prof_cycles
        if self.profile and prof_cycles is not None:
            prof_cycles = prof_cycles + np.where(state.active > 0, nb, 0)
        return SlotState(state.fv, state.fl, *dev, state.active.copy(),
                         base, last, fired, quiesced, disp,
                         cap=state.cap, stalled=stalled,
                         active_dev=active_dev,
                         prof=prof, prof_cycles=prof_cycles,
                         sched=state.sched)

    @_spans_closed_on_error
    def harvest(self, state: SlotState, slot_ids, obs=None
                ) -> tuple[SlotState, list[EngineResult]]:
        """Extract the resident requests' EngineResults from the given
        (active) slots and free them.  Results follow the same
        accounting as run(): cycles = last progress cycle + 1 trailing
        idle cycle, capped at the slot's cycle cap (per-request if the
        admission set one); dispatches = blocks the request rode.

        obs: the calling server's :class:`repro.obs.Probe` (or None): on
        the dynamic path it splits the readback from building results,
        and counts the bytes read back."""
        self._check_slot_api()
        if self._part_on:
            return self._mf_ctx().slot_harvest(state, slot_ids)
        slot_ids = list(slot_ids)
        idle = [b for b in slot_ids if not state.active[b]]
        if idle:
            raise ValueError(f"slots {idle} are free — nothing to harvest")
        read_prof = self.profile and state.prof is not None
        if obs is not None:
            nbytes = state.out_last.nbytes + state.out_count.nbytes + (
                sum(x.nbytes for x in state.prof) if read_prof else 0)
            obs.count("d2h_bytes", nbytes, site="harvest")
            sp = obs.begin("dataflow.harvest.d2h", bytes=nbytes)
        out_last, out_count = jax.device_get((state.out_last,
                                              state.out_count))
        prof = jax.device_get(state.prof) if read_prof else None
        if obs is not None:
            obs.end(sp)
            sp = obs.begin("dataflow.harvest.results")

        def _prof_row(b):
            # scheduled engines accrue §12 counters on the host from the
            # plan (closed form); dynamic engines read the device rows
            if self.profile and state.sched is not None:
                return (*state.sched.prof_row(b),
                        int(state.prof_cycles[b]),
                        int(state.dispatches[b]))
            if prof is None:
                return None
            return (*(x[b] for x in prof), int(state.prof_cycles[b]),
                    int(state.dispatches[b]))
        results = [self._result_from_state(
            out_last[b], out_count[b],
            int(min(state.last[b] + 1, state.cap[b])),
            int(state.fired[b]), int(state.dispatches[b]),
            prof=_prof_row(b))
            for b in slot_ids]
        active = state.active.copy()
        quiesced = state.quiesced.copy()
        active[slot_ids] = 0
        quiesced[slot_ids] = False
        if obs is not None:
            obs.end(sp)
        return dataclasses.replace(state, active=active, quiesced=quiesced,
                                   active_dev=jnp.asarray(active)), results

    # -- pallas run()/run_batch(): one wave of the slot API -------------
    def _wave(self, feeds_batch, max_cycles: int) -> list[EngineResult]:
        """Run B streams as one admission round of the slot API: all
        admitted at once with ``max_cycles`` as their cap, stepped a
        block per dispatch (never past the cap) until the last one
        quiesces, then harvested together.  Every slot stays active to
        the end (idle is absorbing, so stepping a quiesced slot changes
        nothing), and each result reports the wave's blocks as its
        dispatches.  The state is dynamic even on a scheduled engine:
        the wave is that engine's fallback."""
        B = len(feeds_batch)
        state = self.reset_slots(self._init_state(B, scheduled=False),
                                 range(B), feeds_batch,
                                 caps=[max_cycles] * B)
        while not state.quiesced.all() and state.base[0] < max_cycles:
            state = self.step_block(state, min(
                self.block_cycles, max_cycles - int(state.base[0])))
        return self.harvest(state, range(B))[1]

    # -- implementation ---------------------------------------------------
    def _run_impl(self, feed_vals, feed_len, *, max_cycles):
        p = self.p
        A, ts, dtype = p["A"], self.token_shape, self.dtype
        opcode = jnp.asarray(p["opcode"])
        in_idx = jnp.asarray(p["in_idx"])
        out_idx = jnp.asarray(p["out_idx"])
        const_mask = jnp.asarray(p["const_mask"])
        in_arc_idx = jnp.asarray(
            [p["aidx"][a] for a in p["input_arcs"]], jnp.int32).reshape(-1)
        out_arc_idx = jnp.asarray(
            [p["aidx"][a] for a in p["output_arcs"]], jnp.int32).reshape(-1)

        full0 = jnp.zeros((A + 2,), bool).at[p["FULL_PAD"]].set(True)
        full0 = jnp.where(const_mask, True, full0)
        val0 = jnp.zeros((A + 2, *ts), dtype)
        for a, v in self.graph.consts.items():
            val0 = val0.at[p["aidx"][a]].set(jnp.asarray(v, dtype))
        # initial-token annotations: the arc starts full, one shot (not
        # re-asserted by const_mask, so a consumer drains it for good)
        for a, v in self.graph.inits.items():
            full0 = full0.at[p["aidx"][a]].set(True)
            val0 = val0.at[p["aidx"][a]].set(jnp.asarray(v, dtype))

        n_out = max(len(p["output_arcs"]), 1)
        state0 = dict(
            full=full0, val=val0,
            ptr=jnp.zeros((max(len(p["input_arcs"]), 1),), jnp.int32),
            out_last=jnp.zeros((n_out, *ts), dtype),
            out_count=jnp.zeros((n_out,), jnp.int32),
            cycles=jnp.int32(0), fired=jnp.int32(0),
            last_prog=jnp.int32(0),
            progress=jnp.bool_(True),
        )
        profile = self.profile
        if profile:
            nf0, si0, so0, ab0, ahw0 = _prof_zeros(len(self.graph.nodes),
                                                   A + 2)
            state0.update(nf=nf0, si=si0, so=so0, ab=ab0, ahw=ahw0)

        EMPTY_PAD = p["EMPTY_PAD"]
        FULL_PAD = p["FULL_PAD"]
        cs = p["class_slices"]

        def fire_rule_generic(full, val):
            """Dense fire rule: every opcode's ALU result for every node,
            selected by a ~20-way where-chain."""
            inf = full[in_idx]                       # [N,3]
            oute = ~full[out_idx]                    # [N,2]
            a = val[in_idx[:, 0]]
            b = val[in_idx[:, 1]]
            ctrl3 = truthy(val[in_idx[:, 2]])        # dmerge control
            ctrl2 = truthy(b)                        # branch control
            all_in = inf.all(axis=1)
            all_out = oute.all(axis=1)

            is_nd = opcode == int(Op.NDMERGE)
            is_dm = opcode == int(Op.DMERGE)
            is_br = opcode == int(Op.BRANCH)

            dm_chosen_full = jnp.where(ctrl3, inf[:, 0], inf[:, 1])
            ready = all_in & all_out
            ready = jnp.where(is_nd, (inf[:, 0] | inf[:, 1]) & all_out,
                              ready)
            ready = jnp.where(is_dm, inf[:, 2] & dm_chosen_full & all_out,
                              ready)
            ready = jnp.where(
                is_br,
                inf[:, 0] & inf[:, 1]
                & jnp.where(ctrl2, oute[:, 0], oute[:, 1]),
                ready)

            # operand/result values
            nd_val = jnp.where(_expand(inf[:, 0], ts), a, b)
            dm_val = jnp.where(_expand(ctrl3, ts), a, b)
            z = a  # default (COPY / BRANCH route a; SINK ignores)
            for op in ALU_OPS:
                z = jnp.where(_expand(opcode == int(op), ts),
                              alu(op, a, b, dtype), z)
            z = jnp.where(_expand(is_nd, ts), nd_val, z)
            z = jnp.where(_expand(is_dm, ts), dm_val, z)

            # consumption mask [N,3]
            consume = ready[:, None] & jnp.ones((1, MAX_IN), bool)
            nd_pick = jnp.stack([inf[:, 0], ~inf[:, 0],
                                 jnp.zeros_like(inf[:, 0])], axis=1)
            dm_pick = jnp.stack([ctrl3, ~ctrl3,
                                 jnp.ones_like(ctrl3)], axis=1)
            consume = jnp.where(is_nd[:, None], ready[:, None] & nd_pick,
                                consume)
            consume = jnp.where(is_dm[:, None], ready[:, None] & dm_pick,
                                consume)

            # production mask [N,2]
            produce = ready[:, None] & jnp.ones((1, MAX_OUT), bool)
            br_pick = jnp.stack([ctrl2, ~ctrl2], axis=1)
            produce = jnp.where(is_br[:, None], ready[:, None] & br_pick,
                                produce)
            return ready, z, consume, produce

        _CTRL = (int(Op.NDMERGE), int(Op.DMERGE), int(Op.BRANCH))
        has_ctrl = cs is not None and any(op in _CTRL for op, _, _ in cs)

        def fire_rule_spec(full, val):
            """Opcode-class-specialized fire rule (DESIGN.md §8): nodes
            are bucketed by opcode in the plan, so a static Python loop
            over only the classes present computes each bucket's exact
            ALU result on its contiguous slice — no dense where-chain,
            and the shift/div guards exist only if SHL/SHR/DIV do.
            Control-free fabrics (every DAG bench) additionally keep
            the uniform ready/consume/produce masks as single whole-
            array ops: only the ALU result is bucketed."""
            inf = full[in_idx]                       # [N,3]
            oute = ~full[out_idx]                    # [N,2]
            a = val[in_idx[:, 0]]
            b = val[in_idx[:, 1]]
            all_in = inf.all(axis=1)
            all_out = oute.all(axis=1)
            base = all_in & all_out
            ones_i = jnp.ones((1, MAX_IN), bool)
            ones_o = jnp.ones((1, MAX_OUT), bool)
            if not has_ctrl:
                z_p = [alu(Op(op), a[lo:hi], b[lo:hi], dtype)
                       for op, lo, hi in cs]
                z = z_p[0] if len(z_p) == 1 else jnp.concatenate(z_p)
                return (base, z, base[:, None] & ones_i,
                        base[:, None] & ones_o)
            r_p, z_p, c_p, p_p = [], [], [], []
            for opi, lo, hi in cs:
                op = Op(opi)
                ak, bk = a[lo:hi], b[lo:hi]
                infk, outek = inf[lo:hi], oute[lo:hi]
                if op == Op.NDMERGE:
                    rk = (infk[:, 0] | infk[:, 1]) & all_out[lo:hi]
                    zk = jnp.where(_expand(infk[:, 0], ts), ak, bk)
                    ck = rk[:, None] & jnp.stack(
                        [infk[:, 0], ~infk[:, 0],
                         jnp.zeros_like(infk[:, 0])], axis=1)
                    pk = rk[:, None] & ones_o
                elif op == Op.DMERGE:
                    c3 = truthy(val[in_idx[lo:hi, 2]])
                    rk = (infk[:, 2]
                          & jnp.where(c3, infk[:, 0], infk[:, 1])
                          & all_out[lo:hi])
                    zk = jnp.where(_expand(c3, ts), ak, bk)
                    ck = rk[:, None] & jnp.stack(
                        [c3, ~c3, jnp.ones_like(c3)], axis=1)
                    pk = rk[:, None] & ones_o
                elif op == Op.BRANCH:
                    c2 = truthy(bk)
                    rk = (infk[:, 0] & infk[:, 1]
                          & jnp.where(c2, outek[:, 0], outek[:, 1]))
                    zk = ak
                    ck = rk[:, None] & ones_i
                    pk = rk[:, None] & jnp.stack([c2, ~c2], axis=1)
                else:
                    rk = base[lo:hi]
                    zk = alu(op, ak, bk, dtype)
                    ck = rk[:, None] & ones_i
                    pk = rk[:, None] & ones_o
                r_p.append(rk)
                z_p.append(zk)
                c_p.append(ck)
                p_p.append(pk)
            return (jnp.concatenate(r_p), jnp.concatenate(z_p),
                    jnp.concatenate(c_p), jnp.concatenate(p_p))

        fire_rule = fire_rule_spec if cs else fire_rule_generic

        def cycle(s):
            full, val = s["full"], s["val"]
            # --- 1. strobe environment input buses -----------------------
            if len(p["input_arcs"]):
                can_feed = (~full[in_arc_idx]) & (s["ptr"] < feed_len)
                nxt = jnp.take_along_axis(
                    feed_vals, s["ptr"].reshape(-1, 1, *([1] * len(ts))),
                    axis=1)[:, 0]
                tgt = jnp.where(can_feed, in_arc_idx, EMPTY_PAD)
                val = val.at[tgt].set(
                    jnp.where(can_feed.reshape(-1, *([1] * len(ts))),
                              nxt, val[tgt]))
                full = full.at[tgt].set(can_feed | full[tgt])
                ptr = s["ptr"] + can_feed
                fed_any = jnp.any(can_feed)
                full = full.at[EMPTY_PAD].set(False)
            else:
                ptr, fed_any = s["ptr"], jnp.bool_(False)

            # --- 2. fire every ready node --------------------------------
            if profile:
                # stall attribution reads the post-feed registers the
                # fire rule is about to see (ready ⊆ inputs_ready)
                ir = inputs_ready(opcode, in_idx, full, val)
            ready, z, consume, produce = fire_rule(full, val)
            pvals = jnp.stack([z, z], axis=1)        # [N,2,*ts]

            # scatter: consume, then produce (see module docstring)
            cidx = jnp.where(consume, in_idx, EMPTY_PAD).reshape(-1)
            full = full.at[cidx].set(False)
            pidx = jnp.where(produce, out_idx, EMPTY_PAD).reshape(-1)
            full = full.at[pidx].set(True)
            val = val.at[pidx].set(pvals.reshape(-1, *ts))
            # restore dummy slots
            full = full.at[FULL_PAD].set(True)
            full = full.at[EMPTY_PAD].set(False)
            full = jnp.where(const_mask, True, full)

            if profile:
                # occupancy sample point: post-fire, pre-drain — a
                # produced output token counts busy the cycle it exists
                occ = full.astype(jnp.int32).at[FULL_PAD].set(0) \
                          .at[EMPTY_PAD].set(0)
                prof_upd = dict(
                    nf=s["nf"] + ready,
                    si=s["si"] + ~ir,
                    so=s["so"] + (ir & ~ready),
                    ab=s["ab"] + occ,
                    ahw=jnp.maximum(s["ahw"], occ))
            else:
                prof_upd = {}

            # --- 3. environment drains output buses ----------------------
            if len(p["output_arcs"]):
                got = full[out_arc_idx]
                out_last = jnp.where(_expand(got, ts), val[out_arc_idx],
                                     s["out_last"])
                out_count = s["out_count"] + got
                full = full.at[out_arc_idx].set(False)
                drained_any = jnp.any(got)
            else:
                out_last, out_count = s["out_last"], s["out_count"]
                drained_any = jnp.bool_(False)

            n_fired = jnp.sum(ready.astype(jnp.int32))
            prog = fed_any | drained_any | (n_fired > 0)
            return dict(
                full=full, val=val, ptr=ptr, out_last=out_last,
                out_count=out_count, cycles=s["cycles"] + 1,
                fired=s["fired"] + n_fired,
                last_prog=jnp.where(prog, s["cycles"] + 1, s["last_prog"]),
                progress=prog, **prof_upd)

        def block(s):
            # K fused cycles per while_loop iteration; quiescence is only
            # inspected at block granularity.  `progress` of the block's
            # LAST cycle decides continuation: an idle cycle is absorbing
            # (no feed/fire/drain can re-arm without one of the others),
            # so tail-idle == quiescent.
            return jax.lax.fori_loop(0, self.block_cycles,
                                     lambda i, s: cycle(s), s)

        def cond(s):
            # only admit blocks that fit entirely under the cap; the
            # max_cycles % K remainder runs below, so a cutoff simulates
            # EXACTLY max_cycles cycles (bit-identical fired/counts to
            # the per-cycle reference even mid-activity).
            return s["progress"] & (s["cycles"] + self.block_cycles
                                    <= max_cycles)

        s = jax.lax.while_loop(cond, block, state0)
        s = jax.lax.fori_loop(0, max_cycles % self.block_cycles,
                              lambda i, s: cycle(s), s)
        # reported cycles = last progress cycle + 1 trailing idle cycle,
        # exactly the per-cycle reference count, regardless of block
        # overrun past quiescence.
        cycles = jnp.minimum(s["last_prog"] + 1, max_cycles)
        if profile:
            # counters cover every SIMULATED cycle (s["cycles"]): block
            # overrun past quiescence adds idle cycles that fire nothing
            return (s["out_last"], s["out_count"], cycles, s["fired"],
                    s["nf"], s["si"], s["so"], s["ab"], s["ahw"],
                    s["cycles"])
        return s["out_last"], s["out_count"], cycles, s["fired"]


def _expand(mask, ts):
    return mask.reshape(*mask.shape, *([1] * len(ts)))


# ---------------------------------------------------------------------------
# Pure-numpy reference engine (oracle for property tests + Pallas kernel ref)
# ---------------------------------------------------------------------------
def run_reference(graph: Graph, feeds=None, token_shape=(), dtype=np.int32,
                  max_cycles: int = 100_000, trace=None,
                  profile: bool = False) -> EngineResult:
    """Slow, obviously-correct mirror of :class:`DataflowEngine`.

    trace: optional callback receiving (cycle, node_index, value) for
    every firing — used e.g. to extract pipeline schedules
    (core/pipeline.py).  profile=True additionally accumulates the
    DESIGN.md §12 fabric counters (the oracle for the device backends'
    profiled runs).  One errstate for the whole run: integer
    wraparound / float specials are the ALU contract (see
    :func:`repro.core.alu.alu_numpy`), and entering a context manager per firing
    would tax the per-node python loop."""
    with np.errstate(all="ignore"):
        return _run_reference(graph, feeds, token_shape, dtype,
                              max_cycles, trace, profile)


def _run_reference(graph, feeds, token_shape, dtype, max_cycles,
                   trace, profile=False) -> EngineResult:
    p = plan(graph)
    feeds = {a: np.asarray(v, dtype).reshape(-1, *token_shape)
             if np.asarray(v).ndim == 1 and token_shape == ()
             else np.broadcast_to(
                 np.asarray(v, dtype).reshape(np.shape(v)[0],
                                              *([1] * len(token_shape))),
                 (np.shape(v)[0], *token_shape))
             if np.asarray(v).ndim == 1
             else np.asarray(v, dtype)
             for a, v in (feeds or {}).items()}
    full = {a: False for a in p["arcs"]}
    val = {a: np.zeros(token_shape, dtype) for a in p["arcs"]}
    for a, v in graph.consts.items():
        full[a] = True
        val[a] = np.full(token_shape, v, dtype)
    for a, v in graph.inits.items():    # one-shot initial tokens
        full[a] = True
        val[a] = np.full(token_shape, v, dtype)
    ptr = {a: 0 for a in p["input_arcs"]}
    out_last = {a: np.zeros(token_shape, dtype) for a in p["output_arcs"]}
    out_count = {a: 0 for a in p["output_arcs"]}

    def compute(op, a, b):
        return _alu_numpy(op, a, b, dtype)   # caller holds the errstate

    def truthy(v):
        return np.asarray(v).ravel()[0] != 0

    N = len(graph.nodes)
    if profile:
        nf = np.zeros((N,), np.int64)
        si = np.zeros((N,), np.int64)
        so = np.zeros((N,), np.int64)
        ab = np.zeros((len(p["arcs"]),), np.int64)
        ahw = np.zeros((len(p["arcs"]),), np.int64)

    def node_inputs_ready(n, sfull, sval):
        """Mirror of :func:`repro.core.alu.inputs_ready` on the dict
        registers."""
        i = n.inputs
        if n.op == Op.NDMERGE:
            return sfull[i[0]] or sfull[i[1]]
        if n.op == Op.DMERGE:
            if not sfull[i[2]]:
                return False
            return sfull[i[0]] if truthy(sval[i[2]]) else sfull[i[1]]
        return all(sfull[x] for x in i)

    cycles = fired = 0
    progress = True
    while progress and cycles < max_cycles:
        progress = False
        # 1. feed
        for a in p["input_arcs"]:
            if not full[a] and a in feeds and ptr[a] < len(feeds[a]):
                val[a] = feeds[a][ptr[a]]
                full[a] = True
                ptr[a] += 1
                progress = True
        # 2. fire (simultaneous: snapshot)
        sfull = dict(full)
        sval = dict(val)
        plans = []
        for n_idx, n in enumerate(graph.nodes):
            i = n.inputs
            o = n.outputs
            if n.op == Op.NDMERGE:
                rdy = (sfull[i[0]] or sfull[i[1]]) and not sfull[o[0]]
                if rdy:
                    src = i[0] if sfull[i[0]] else i[1]
                    plans.append((n_idx, [src], [(o[0], sval[src])]))
            elif n.op == Op.DMERGE:
                if sfull[i[2]]:
                    src = i[0] if truthy(sval[i[2]]) else i[1]
                    if sfull[src] and not sfull[o[0]]:
                        plans.append((n_idx, [src, i[2]],
                                      [(o[0], sval[src])]))
            elif n.op == Op.BRANCH:
                if sfull[i[0]] and sfull[i[1]]:
                    dst = o[0] if truthy(sval[i[1]]) else o[1]
                    if not sfull[dst]:
                        plans.append((n_idx, list(i), [(dst, sval[i[0]])]))
            else:
                if all(sfull[x] for x in i) and not any(sfull[x] for x in o):
                    aop = sval[i[0]]
                    bop = sval[i[1]] if len(i) > 1 else aop
                    z = compute(n.op, aop, bop)
                    plans.append((n_idx, list(i), [(x, z) for x in o]))
        for n_idx, cons, prods in plans:
            for x in cons:
                full[x] = False
            for x, v in prods:
                full[x] = True
                val[x] = v
            if trace is not None:
                tv = prods[0][1] if prods else val.get(cons[0], 0)
                trace((cycles + 1, n_idx, int(np.asarray(tv).ravel()[0])))
            fired += 1
            progress = True
        for a in graph.consts:
            full[a] = True
        if profile:
            fired_set = {n_idx for n_idx, _, _ in plans}
            for n_idx, n in enumerate(graph.nodes):
                if n_idx in fired_set:
                    nf[n_idx] += 1
                elif node_inputs_ready(n, sfull, sval):
                    so[n_idx] += 1
                else:
                    si[n_idx] += 1
            # occupancy sample point: post-fire, pre-drain
            for k, a in enumerate(p["arcs"]):
                if full[a]:
                    ab[k] += 1
                    ahw[k] = 1
        # 3. drain
        for a in p["output_arcs"]:
            if full[a]:
                out_last[a] = val[a]
                out_count[a] += 1
                full[a] = False
                progress = True
        cycles += 1
    prof_obj = node_fires = None
    if profile:
        from repro.obs.profile import FabricProfile
        node_names, arc_names = FabricProfile.names_for(graph)
        prof_obj = FabricProfile(
            node_names=node_names, arc_names=arc_names,
            node_fires=nf, stall_in=si, stall_out=so,
            arc_busy=ab, arc_hw=ahw, cycles=cycles, dispatches=0)
        node_fires = nf
    return EngineResult(outputs=out_last, counts=out_count, cycles=cycles,
                        fired=fired, node_fires=node_fires,
                        profile=prof_obj)
