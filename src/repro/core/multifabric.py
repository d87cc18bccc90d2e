"""Multi-fabric sharded execution: one graph as P communicating fabrics.

A :class:`~repro.core.partition.Partition` splits the graph into P
regions; each region compiles to an independent fabric plan (the same
:func:`repro.core.plan.plan` layout the solo engine uses, including
the role-ordered arc permutation under ``optimize``) and every
inter-region arc becomes a *token channel* — a replicated
(full, value) register pair that both endpoint fabrics see.  Execution
runs SPMD over a ``"shards"`` axis: under ``shard_map`` on a device
mesh when the platform has >= P devices (CPU CI forces host devices via
``--xla_force_host_platform_device_count``), or under
``jax.vmap(axis_name="shards")`` on a single device — the two paths
trace the *same* per-shard program, so they are bit-identical.

Lockstep channel semantics (DESIGN.md §14).  A depth-1 arc couples its
endpoints in BOTH directions every cycle — the token moves forward and
the backpressure (full bit) moves backward — so regions cannot run
decoupled and stay bit-identical to the solo fabric.  Instead every
region executes the global cycle against a consistent snapshot:

1. mirror the replicated channel registers into the region's local arc
   slots (both endpoints now see the true global state);
2. run the solo engine's exact cycle body (feed -> fire -> drain) on
   the region's own nodes;
3. each endpoint owner reports its delta — the producer region's
   *push* (token + value), the consumer region's *consume* — and one
   ``lax.psum`` over the shards axis merges them:
   ``full' = (full & ~consumed) | pushed``, exactly the register
   update an internal arc performs in the solo engine.

The per-cycle merges are fused *inside* the compiled K-cycle block, so
the host still sees one device dispatch per block and the only
cross-device communication is the channel-register exchange.  The
K-deep ring the channels ride is the per-block history of those K
merged slots: depth K absorbs the whole block-fused skew window, which
is why block granularity never changes results (quiescence is detected
from the merged global progress bit, again identical to solo).

Bit-identity in every :class:`~repro.core.engine.EngineResult` field
(outputs, counts, cycles, fired, node_fires, merged profile) holds by
construction and is property-tested in ``tests/test_partition.py``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from repro.core.alu import ALU_OPS, alu, inputs_ready, truthy
from repro.core.engine import EngineResult, SlotState, pack_feeds
from repro.core.graph import Graph, Op
from repro.core.partition import Partition
from repro.core.plan import MAX_IN, MAX_OUT, plan


@jax.jit
def _mf_slot_reset(fv, fl, full, val, ptr, out_last, out_count, chf, chv,
                   mask, fv_rows, fl_rows, full0, val0, chf0, chv0):
    """Masked fused admission reset over [P, B, ...] slot state (the
    multi-fabric mirror of engine._slot_reset; one dispatch per round)."""
    m4 = mask[None, :, None, None]
    m3 = mask[None, :, None]
    return (jnp.where(m4, fv_rows, fv),
            jnp.where(m3, fl_rows, fl),
            jnp.where(m3, full0[:, None, :], full),
            jnp.where(m3, val0[:, None, :], val),
            jnp.where(m3, 0, ptr),
            jnp.where(m3, jnp.zeros((), out_last.dtype), out_last),
            jnp.where(m3, 0, out_count),
            jnp.where(m3, chf0[None, None, :], chf),
            jnp.where(m3, chv0[None, None, :], chv))


@jax.jit
def _mf_prof_reset(prof, mask):
    """Zero the masked slots' [P, B, ...] profile counters."""
    return tuple(jnp.where(mask[None, :, None], 0, x) for x in prof)


class MultiFabric:
    """P cooperating fabric plans + replicated token channels.

    Owned by a :class:`~repro.core.engine.DataflowEngine` constructed
    with ``partition`` engaged (P > 1); the engine delegates
    ``run``/``run_batch`` and the whole resumable slot API here.  The
    host-side block loop and all cycle accounting mirror the engine's
    pallas host loop exactly, so reported cycles/dispatches follow the
    same rules as every other backend.
    """

    def __init__(self, graph: Graph, part: Partition, *,
                 dtype=jnp.int32, block_cycles: int = 16,
                 optimize: bool = False, profile: bool = False,
                 max_cycles: int = 100_000, placement: str = "auto"):
        self.graph = graph
        self.part = part
        self.P = part.P
        self.dtype = jnp.dtype(dtype)
        self._np_dtype = np.dtype(str(self.dtype))
        self.block_cycles = int(block_cycles)
        self.optimize = bool(optimize)
        self.profile = bool(profile)
        self.max_cycles = int(max_cycles)
        self._build_tables()
        n_dev = len(jax.devices())
        if placement == "shard_map" and n_dev < self.P:
            raise ValueError(
                f"placement='shard_map' needs >= {self.P} devices, "
                f"have {n_dev}")
        self.use_shard_map = (placement == "shard_map"
                              or (placement == "auto" and n_dev >= self.P))
        self._mesh = (Mesh(np.array(jax.devices()[:self.P]), ("shards",))
                      if self.use_shard_map else None)
        self._tabs = {k: jnp.asarray(v) for k, v in self.tables.items()}
        self._steps: dict[int, object] = {}

    # ------------------------------------------------------------ plan build
    def _build_tables(self):
        g, part = self.graph, self.part
        P, assign = self.P, part.assign
        g.validate()
        prod = {a: ns[0] for a, ns in g.producers().items()}
        cons = g.consumers()
        garc = {a: i for i, a in enumerate(g.arcs)}

        # inter-region arcs -> channels (const buses are replicated,
        # never cut; producer-less / consumer-less arcs stay local)
        self.channels = [
            a for a in g.arcs
            if a not in g.consts and a in prod and a in cons
            and assign[prod[a]] != assign[cons[a][0]]]
        ch_set = set(self.channels)
        self.C = len(self.channels)
        Cp = max(self.C, 1)

        region_nodes = part.regions()
        self.subs: list[Graph] = []
        for r in range(P):
            sub = Graph(name=f"{g.name}@r{r}of{P}")
            used: set[str] = set()
            for i in region_nodes[r]:
                sub.nodes.append(g.nodes[i])
                used.update(g.nodes[i].inputs)
                used.update(g.nodes[i].outputs)
            for a, v in g.consts.items():
                # replicate consumed const buses; a (degenerate)
                # consumer-less const drains from region 0 like solo
                if a in used or (r == 0 and a not in cons):
                    sub.consts[a] = v
            for a, v in g.inits.items():
                # a cut init arc's one-shot token lives in the channel
                # registers; local inits stay with their consumer region
                if a in ch_set:
                    continue
                if assign[cons[a][0]] == r:
                    sub.inits[a] = v
            self.subs.append(sub)
        self.plans = [plan(sub, optimize=self.optimize)
                      for sub in self.subs]

        env_in_all = g.input_arcs()
        env_out_all = g.output_arcs()
        self.graph_inputs = env_in_all
        self.env_in = [[a for a in p["input_arcs"] if a not in ch_set]
                       for p in self.plans]
        env_out = [[a for a in p["output_arcs"] if a not in ch_set]
                   for p in self.plans]
        assert sorted(a for e in self.env_in for a in e) == sorted(env_in_all)
        assert sorted(a for e in env_out for a in e) == sorted(env_out_all)
        # global output arc -> (region, local env row), graph order
        row_of = {(r, a): k for r in range(P)
                  for k, a in enumerate(env_out[r])}
        owner_out = {a: r for r in range(P) for a in env_out[r]}
        self.out_rows = [(a, owner_out[a], row_of[(owner_out[a], a)])
                         for a in env_out_all]

        Nm = max(1, max(len(s.nodes) for s in self.subs))
        A2m = max(p["A"] + 2 for p in self.plans)
        n_in = max(1, max(len(e) for e in self.env_in))
        n_out = max(1, max(len(e) for e in env_out))
        self.Nm, self.A2m, self.n_in, self.n_out = Nm, A2m, n_in, n_out

        opcode = np.zeros((P, Nm), np.int32)
        in_idx = np.zeros((P, Nm, MAX_IN), np.int32)
        out_idx = np.zeros((P, Nm, MAX_OUT), np.int32)
        const_mask = np.zeros((P, A2m), bool)
        full0 = np.zeros((P, A2m), bool)
        val0 = np.zeros((P, A2m), self._np_dtype)
        in_arc_idx = np.zeros((P, n_in), np.int32)
        out_arc_idx = np.zeros((P, n_out), np.int32)
        full_pad = np.zeros((P,), np.int32)
        empty_pad = np.zeros((P,), np.int32)
        node_back = np.full((P, Nm), -1, np.int64)
        arc_back = np.full((P, A2m), -1, np.int64)
        ch_in_pos = np.zeros((P, Cp), np.int32)
        ch_out_pos = np.zeros((P, Cp), np.int32)
        ch_in_own = np.zeros((P, Cp), bool)
        ch_out_own = np.zeros((P, Cp), bool)

        for r, (sub, p) in enumerate(zip(self.subs, self.plans)):
            nr = len(sub.nodes)
            ep = p["EMPTY_PAD"]
            full_pad[r] = p["FULL_PAD"]
            empty_pad[r] = ep
            opcode[r, :nr] = p["opcode"]
            # pad node rows read EMPTY_PAD inputs -> never ready, never
            # fire (the engine's pad convention inverted on purpose)
            in_idx[r] = ep
            out_idx[r] = ep
            in_idx[r, :nr] = p["in_idx"]
            out_idx[r, :nr] = p["out_idx"]
            const_mask[r, :p["A"] + 2] = p["const_mask"]
            full0[r, p["FULL_PAD"]] = True
            for a, v in sub.consts.items():
                full0[r, p["aidx"][a]] = True
                val0[r, p["aidx"][a]] = v
            for a, v in sub.inits.items():
                full0[r, p["aidx"][a]] = True
                val0[r, p["aidx"][a]] = v
            in_arc_idx[r] = ep
            out_arc_idx[r] = ep
            for k, a in enumerate(self.env_in[r]):
                in_arc_idx[r, k] = p["aidx"][a]
            for k, a in enumerate(env_out[r]):
                out_arc_idx[r, k] = p["aidx"][a]
            node_back[r, :nr] = np.asarray(region_nodes[r])[p["node_perm"]]
            for a in p["arcs"]:
                if a not in ch_set:
                    arc_back[r, p["aidx"][a]] = garc[a]
            ch_in_pos[r] = ep
            ch_out_pos[r] = ep

        ch_full0 = np.zeros((Cp,), np.int32)
        ch_val0 = np.zeros((Cp,), self._np_dtype)
        self.ch_rows = np.zeros((self.C,), np.int64)
        for c, a in enumerate(self.channels):
            rU, rD = assign[prod[a]], assign[cons[a][0]]
            ch_out_pos[rU, c] = self.plans[rU]["aidx"][a]
            ch_out_own[rU, c] = True
            ch_in_pos[rD, c] = self.plans[rD]["aidx"][a]
            ch_in_own[rD, c] = True
            self.ch_rows[c] = garc[a]
            if a in g.inits:
                ch_full0[c] = 1
                ch_val0[c] = g.inits[a]

        self._present = tuple(
            int(op) for op in ALU_OPS
            if any(n.op == op for n in g.nodes))
        self.tables = dict(
            opcode=opcode, in_idx=in_idx, out_idx=out_idx,
            const_mask=const_mask, in_arc_idx=in_arc_idx,
            out_arc_idx=out_arc_idx, full_pad=full_pad,
            empty_pad=empty_pad, ch_in_pos=ch_in_pos,
            ch_out_pos=ch_out_pos, ch_in_own=ch_in_own,
            ch_out_own=ch_out_own)
        self.full0, self.val0 = full0, val0
        self.ch_full0, self.ch_val0 = ch_full0, ch_val0
        self.node_back, self.arc_back = node_back, arc_back

    # --------------------------------------------------------- compiled step
    def _core_fn(self, nb: int):
        """Per-shard K-cycle block program over [B, ...] slot state.

        Positional layout (after `tabs`): fv, fl, full, val, ptr,
        out_last, out_count, chf, chv, act, then (profiled only) the 5
        node/arc counters and the 3 channel counters.  Returns the
        persistent state + per-block (fired, last_progress) per slot.
        """
        profiled = self.profile
        present = self._present
        dtype = self.dtype

        def core(tabs, fv, fl, full, val, ptr, out_last, out_count,
                 chf, chv, act, *prof):
            opcode = tabs["opcode"]
            in_idx = tabs["in_idx"]
            out_idx = tabs["out_idx"]
            const_mask = tabs["const_mask"]
            FULL_PAD = tabs["full_pad"]
            EMPTY_PAD = tabs["empty_pad"]
            in_arc_idx = tabs["in_arc_idx"]
            out_arc_idx = tabs["out_arc_idx"]
            cip, cop = tabs["ch_in_pos"], tabs["ch_out_pos"]
            cio, coo = tabs["ch_in_own"], tabs["ch_out_own"]
            ch_pos = jnp.concatenate([cip, cop])

            def fire(full, val):
                # the solo engine's generic fire rule, with the ALU
                # where-chain restricted to the opcodes present in the
                # graph (the SPMD-compatible share of DESIGN.md §8's
                # opcode specialization — per-region class slices would
                # need per-shard programs, which SPMD forbids)
                inf = full[in_idx]                    # [N,3]
                oute = ~full[out_idx]                 # [N,2]
                a = val[in_idx[:, 0]]
                b = val[in_idx[:, 1]]
                ctrl3 = truthy(val[in_idx[:, 2]])
                ctrl2 = truthy(b)
                all_in = inf.all(axis=1)
                all_out = oute.all(axis=1)
                is_nd = opcode == int(Op.NDMERGE)
                is_dm = opcode == int(Op.DMERGE)
                is_br = opcode == int(Op.BRANCH)
                dm_chosen = jnp.where(ctrl3, inf[:, 0], inf[:, 1])
                ready = all_in & all_out
                ready = jnp.where(is_nd, (inf[:, 0] | inf[:, 1]) & all_out,
                                  ready)
                ready = jnp.where(is_dm, inf[:, 2] & dm_chosen & all_out,
                                  ready)
                ready = jnp.where(
                    is_br,
                    inf[:, 0] & inf[:, 1]
                    & jnp.where(ctrl2, oute[:, 0], oute[:, 1]), ready)
                z = a
                for op in present:
                    z = jnp.where(opcode == op,
                                  alu(Op(op), a, b, dtype), z)
                z = jnp.where(is_nd, jnp.where(inf[:, 0], a, b), z)
                z = jnp.where(is_dm, jnp.where(ctrl3, a, b), z)
                consume = ready[:, None] & jnp.ones((1, MAX_IN), bool)
                nd_pick = jnp.stack([inf[:, 0], ~inf[:, 0],
                                     jnp.zeros_like(inf[:, 0])], axis=1)
                dm_pick = jnp.stack([ctrl3, ~ctrl3,
                                     jnp.ones_like(ctrl3)], axis=1)
                consume = jnp.where(is_nd[:, None],
                                    ready[:, None] & nd_pick, consume)
                consume = jnp.where(is_dm[:, None],
                                    ready[:, None] & dm_pick, consume)
                produce = ready[:, None] & jnp.ones((1, MAX_OUT), bool)
                br_pick = jnp.stack([ctrl2, ~ctrl2], axis=1)
                produce = jnp.where(is_br[:, None],
                                    ready[:, None] & br_pick, produce)
                return ready, z, consume, produce

            def cycle1(cyc, fv1, fl1, full, val, ptr, out_last, out_count,
                       chf, chv, lp, fired, *profc):
                # 1. mirror the replicated channel registers into both
                #    endpoint regions' local arc slots (consistent
                #    global snapshot; non-owner rows write EMPTY_PAD,
                #    which is re-cleared right after)
                cf = chf > 0
                full = full.at[ch_pos].set(jnp.concatenate([cf, cf]))
                val = val.at[ch_pos].set(jnp.concatenate([chv, chv]))
                full = full.at[FULL_PAD].set(True).at[EMPTY_PAD].set(False)
                # 2. strobe environment input buses (engine cycle step 1)
                can_feed = (~full[in_arc_idx]) & (ptr < fl1)
                nxt = jnp.take_along_axis(fv1, ptr[:, None], axis=1)[:, 0]
                tgt = jnp.where(can_feed, in_arc_idx, EMPTY_PAD)
                val = val.at[tgt].set(jnp.where(can_feed, nxt, val[tgt]))
                full = full.at[tgt].set(can_feed | full[tgt])
                ptr = ptr + can_feed
                fed_any = jnp.any(can_feed)
                full = full.at[EMPTY_PAD].set(False)
                # 3. fire every ready node (engine cycle step 2)
                if profiled:
                    ir = inputs_ready(opcode, in_idx, full, val)
                ready, z, consume, produce = fire(full, val)
                cidx = jnp.where(consume, in_idx, EMPTY_PAD).reshape(-1)
                full = full.at[cidx].set(False)
                pidx = jnp.where(produce, out_idx, EMPTY_PAD).reshape(-1)
                full = full.at[pidx].set(True)
                val = val.at[pidx].set(jnp.stack([z, z], 1).reshape(-1))
                full = full.at[FULL_PAD].set(True).at[EMPTY_PAD].set(False)
                full = jnp.where(const_mask, True, full)
                # 4. channel deltas: the producer owner pushes a fresh
                #    token, the consumer owner reports consumption
                push = coo & (~cf) & full[cop]
                consd = cio & cf & (~full[cip])
                if profiled:
                    # occupancy sample point: post-fire, pre-drain;
                    # channel arcs are sampled from the MERGED register
                    # below (the local copy of the far endpoint's slot
                    # is one cycle stale by construction)
                    occ = full.astype(jnp.int32)
                    occ = occ.at[jnp.where(cio, cip, EMPTY_PAD)].set(0)
                    occ = occ.at[jnp.where(coo, cop, EMPTY_PAD)].set(0)
                    occ = occ.at[FULL_PAD].set(0).at[EMPTY_PAD].set(0)
                # 5. environment drains output buses (engine cycle step 3)
                got = full[out_arc_idx]
                out_last = jnp.where(got, val[out_arc_idx], out_last)
                out_count = out_count + got
                full = full.at[out_arc_idx].set(False)
                drained_any = jnp.any(got)
                n_fired = jnp.sum(ready.astype(jnp.int32))
                prog_l = (fed_any | drained_any
                          | (n_fired > 0)).astype(jnp.int32)
                # 6. one all-reduce merges every cross-region effect:
                #    full' = (full & ~consumed) | pushed  (the solo
                #    register update), plus the global progress bit
                if jnp.issubdtype(dtype, jnp.integer):
                    pv = jnp.where(push, val[cop],
                                   jnp.zeros((), dtype))
                    pg, cg, prg, pvg = lax.psum(
                        (push.astype(jnp.int32), consd.astype(jnp.int32),
                         prog_l, pv), "shards")
                else:
                    # exactly one shard contributes: sum the BITS so
                    # float payloads (incl. -0.0 and NaN) survive intact
                    bits = jnp.dtype(f"int{dtype.itemsize * 8}")
                    pvb = jnp.where(
                        push, lax.bitcast_convert_type(val[cop], bits),
                        jnp.zeros((), bits))
                    pg, cg, prg, pvb = lax.psum(
                        (push.astype(jnp.int32), consd.astype(jnp.int32),
                         prog_l, pvb), "shards")
                    pvg = lax.bitcast_convert_type(pvb, dtype)
                cf2 = (cf & (cg == 0)) | (pg > 0)
                chf = cf2.astype(jnp.int32)
                chv = jnp.where(pg > 0, pvg, chv)
                lp = jnp.where(prg > 0, cyc + 1, lp)
                fired = fired + n_fired
                if profiled:
                    nf, si, so, ab, ahw, cb, chw, cpu = profc
                    c32 = cf2.astype(jnp.int32)
                    profc = (nf + ready, si + ~ir, so + (ir & ~ready),
                             ab + occ, jnp.maximum(ahw, occ),
                             cb + c32, jnp.maximum(chw, c32),
                             cpu + (pg > 0))
                return (full, val, ptr, out_last, out_count, chf, chv,
                        lp, fired, *profc)

            nprof = 8 if profiled else 0
            vcycle = jax.vmap(cycle1, in_axes=(None,) + (0,) * (11 + nprof))
            B = full.shape[0]
            z32 = jnp.zeros((B,), jnp.int32)
            carry = (full, val, ptr, out_last, out_count, chf, chv,
                     z32, z32, *prof)

            def body(i, c):
                return vcycle(i, fv, fl, c[0], c[1], c[2], c[3], c[4],
                              c[5], c[6], c[7], c[8], *c[9:])

            out = lax.fori_loop(0, nb, body, carry)
            # clock-gate: a free slot's block never happened — state,
            # channels and counters revert, fired/lp report 0 (the
            # dataflow_fire.fire_block_masked_ref contract)
            actb = act > 0

            def sel(new, old):
                m = actb.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(m, new, old)

            keep = [sel(n, o) for n, o in zip(
                (out[0], out[1], out[2], out[3], out[4], out[5], out[6]),
                (full, val, ptr, out_last, out_count, chf, chv))]
            profk = [sel(n, o) for n, o in zip(out[9:], prof)]
            f = jnp.where(actb, out[8], 0)
            lp = jnp.where(actb, out[7], 0)
            return (*keep, f, lp, *profk)

        return core

    def _step(self, nb: int):
        step = self._steps.get(nb)
        if step is None:
            core = self._core_fn(nb)
            if self._mesh is not None:
                def stacked(tabs, *args):
                    sq = jax.tree.map(lambda x: x[0], (tabs, *args))
                    out = core(*sq)
                    return jax.tree.map(lambda x: x[None], out)
                spec = PartitionSpec("shards")
                step = jax.jit(jax.shard_map(
                    stacked, mesh=self._mesh, in_specs=spec,
                    out_specs=spec, check_vma=False))
            else:
                step = jax.jit(jax.vmap(core, axis_name="shards"))
            self._steps[nb] = step
        return step

    # ----------------------------------------------------------- host state
    def _fresh_state(self, B: int):
        P, A2m = self.P, self.A2m
        full = np.broadcast_to(self.full0[:, None, :], (P, B, A2m)).copy()
        val = np.broadcast_to(self.val0[:, None, :], (P, B, A2m)).copy()
        chf = np.broadcast_to(self.ch_full0[None, None, :],
                              (P, B, self.ch_full0.shape[0])).copy()
        chv = np.broadcast_to(self.ch_val0[None, None, :],
                              (P, B, self.ch_val0.shape[0])).copy()
        return (jnp.asarray(full), jnp.asarray(val),
                jnp.zeros((P, B, self.n_in), jnp.int32),
                jnp.zeros((P, B, self.n_out), self.dtype),
                jnp.zeros((P, B, self.n_out), jnp.int32),
                jnp.asarray(chf), jnp.asarray(chv))

    def _prof0(self, B: int):
        z = lambda n: jnp.zeros((self.P, B, n), jnp.int32)
        return (z(self.Nm), z(self.Nm), z(self.Nm),
                z(self.A2m), z(self.A2m))

    def _chprof0(self, B: int):
        z = lambda: jnp.zeros((self.P, B, self.ch_full0.shape[0]),
                              jnp.int32)
        return (z(), z(), z())

    def _pack(self, feeds_batch, L_min=1):
        """[P, B, n_in, L] / [P, B, n_in] stacked region feed tables."""
        B = len(feeds_batch)
        L = max([L_min] + [np.shape(v)[0] for f in feeds_batch
                           for v in (f or {}).values()])
        fv = np.zeros((self.P, B, self.n_in, L), self._np_dtype)
        fl = np.zeros((self.P, B, self.n_in), np.int32)
        for b, f in enumerate(feeds_batch):
            f = dict(f or {})
            unknown = set(f) - set(self.graph_inputs)
            if unknown:
                raise ValueError(
                    f"feeds for non-input arcs: {sorted(unknown)}")
            for r in range(self.P):
                sub_f = {a: f[a] for a in self.env_in[r] if a in f}
                pfv, pfl = pack_feeds(self.env_in[r], sub_f, (),
                                      self._np_dtype,
                                      pad_rows=self.n_in, min_len=L)
                fv[r, b] = pfv
                fl[r, b] = pfl
        return fv, fl

    # ------------------------------------------------------------ run paths
    def run(self, feeds=None, max_cycles: int | None = None) -> EngineResult:
        return self.run_batch([feeds or {}], max_cycles)[0]

    def run_batch(self, feeds_batch, max_cycles: int | None = None
                  ) -> list[EngineResult]:
        max_cycles = max_cycles or self.max_cycles
        feeds_batch = list(feeds_batch)
        B = len(feeds_batch)
        fv, fl = self._pack(feeds_batch)
        fv, fl = jnp.asarray(fv), jnp.asarray(fl)
        state = self._fresh_state(B)
        act = jnp.ones((self.P, B), jnp.int32)
        prof = ((*self._prof0(B), *self._chprof0(B))
                if self.profile else ())
        base = dispatches = 0
        last = np.zeros((B,), np.int64)
        fired = np.zeros((B,), np.int64)
        # the engine's pallas host loop, verbatim accounting
        while True:
            nb = min(self.block_cycles, max_cycles - base)
            out = self._step(nb)(self._tabs, fv, fl, *state, act, *prof)
            state, f, lp = out[:7], out[7], out[8]
            prof = tuple(out[9:])
            dispatches += 1
            f, lp = jax.device_get((f, lp))
            fired += np.asarray(f).sum(axis=0)       # regions partition N
            lp = np.asarray(lp)[0]                   # replicated via psum
            last = np.where(lp > 0, base + lp, last)
            base += nb
            if (lp < nb).all() or base >= max_cycles:
                break
        out_last, out_count = jax.device_get((state[3], state[4]))
        hprof = jax.device_get(prof) if self.profile else None
        return [self._result(out_last, out_count,
                             int(min(last[b] + 1, max_cycles)),
                             int(fired[b]), dispatches, b, hprof,
                             prof_cycles=base)
                for b in range(B)]

    def _result(self, out_last, out_count, cycles, fired, dispatches, b,
                hprof, prof_cycles) -> EngineResult:
        outputs = {a: out_last[r][b][k] for a, r, k in self.out_rows}
        counts = {a: int(out_count[r][b][k]) for a, r, k in self.out_rows}
        profile = node_fires = None
        if hprof is not None:
            profile = self.merged_profile(
                [x[:, b] for x in hprof[:5]],
                [x[0, b, :self.C] for x in hprof[5:]],
                cycles=prof_cycles, dispatches=dispatches)
            node_fires = profile.node_fires
        return EngineResult(outputs=outputs, counts=counts, cycles=cycles,
                            fired=fired, dispatches=dispatches,
                            node_fires=node_fires, profile=profile)

    def merged_profile(self, prof, chprof, cycles: int, dispatches: int):
        """Graph-order FabricProfile from per-region [P, ...] counters
        plus the replicated per-channel counters."""
        from repro.obs.profile import FabricProfile
        nf, si, so, ab, ahw = [np.asarray(x, np.int64) for x in prof]
        cb, chw, cpu = [np.asarray(x, np.int64) for x in chprof]
        N, A = len(self.graph.nodes), len(self.graph.arcs)
        gnf, gsi, gso = (np.zeros((N,), np.int64) for _ in range(3))
        gab, gahw = (np.zeros((A,), np.int64) for _ in range(2))
        nv = self.node_back >= 0
        gnf[self.node_back[nv]] = nf[nv]
        gsi[self.node_back[nv]] = si[nv]
        gso[self.node_back[nv]] = so[nv]
        av = self.arc_back >= 0
        gab[self.arc_back[av]] = ab[av]
        gahw[self.arc_back[av]] = ahw[av]
        if self.C:
            gab[self.ch_rows] = cb
            gahw[self.ch_rows] = chw
        node_names, arc_names = FabricProfile.names_for(self.graph)
        return FabricProfile(
            node_names=node_names, arc_names=arc_names,
            node_fires=gnf, stall_in=gsi, stall_out=gso,
            arc_busy=gab, arc_hw=gahw, cycles=int(cycles),
            dispatches=int(dispatches),
            ch_names=list(self.channels),
            ch_busy=cb if self.C else None,
            ch_hw=chw if self.C else None,
            ch_pushes=cpu if self.C else None,
            ch_depth=self.block_cycles)

    # ---------------------------------------------------------- slot API
    def slot_init(self, slots: int) -> SlotState:
        B = int(slots)
        full, val, ptr, out_last, out_count, chf, chv = \
            self._fresh_state(B)
        z64 = lambda: np.zeros((B,), np.int64)
        return SlotState(
            fv=jnp.zeros((self.P, B, self.n_in, 1), self.dtype),
            fl=jnp.zeros((self.P, B, self.n_in), jnp.int32),
            full=full, val=val, ptr=ptr,
            out_last=out_last, out_count=out_count,
            active=np.zeros((B,), np.int32), base=z64(), last=z64(),
            fired=z64(), quiesced=np.zeros((B,), bool), dispatches=z64(),
            cap=np.full((B,), self.max_cycles, np.int64), stalled=z64(),
            active_dev=jnp.zeros((self.P, B), jnp.int32),
            prof=self._prof0(B) if self.profile else None,
            prof_cycles=z64() if self.profile else None,
            mf=dict(chf=chf, chv=chv,
                    chprof=self._chprof0(B) if self.profile else None))

    def slot_reset(self, state: SlotState, slot_ids, new_feeds,
                   caps=None) -> SlotState:
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
        B = state.slots
        L = state.fv.shape[-1]
        pfv, pfl = self._pack(new_feeds, L_min=1)
        need = pfv.shape[-1]
        if need > L:        # grow the stream buffer (pow2 bounds retraces)
            L = 1 << (int(need) - 1).bit_length()
            state = dataclasses.replace(
                state, fv=jnp.pad(
                    state.fv,
                    ((0, 0), (0, 0), (0, 0), (0, L - state.fv.shape[-1]))))
        mask = np.zeros((B,), bool)
        fv_rows = np.zeros((self.P, B, self.n_in, L), self._np_dtype)
        fl_rows = np.zeros((self.P, B, self.n_in), np.int32)
        for j, b in enumerate(slot_ids):
            mask[b] = True
            fv_rows[:, b, :, :pfv.shape[-1]] = pfv[:, j]
            fl_rows[:, b] = pfl[:, j]
        fv_, fl_, full, val, ptr, out_last, out_count, chf, chv = \
            _mf_slot_reset(state.fv, state.fl, state.full, state.val,
                           state.ptr, state.out_last, state.out_count,
                           state.mf["chf"], state.mf["chv"],
                           jnp.asarray(mask), jnp.asarray(fv_rows),
                           jnp.asarray(fl_rows), jnp.asarray(self.full0),
                           jnp.asarray(self.val0),
                           jnp.asarray(self.ch_full0),
                           jnp.asarray(self.ch_val0))
        if caps is None:
            caps = [None] * len(slot_ids)
        if len(caps) != len(slot_ids):
            raise ValueError(f"{len(slot_ids)} slot ids but "
                             f"{len(caps)} caps")
        active = state.active.copy()
        for host in (base := state.base.copy(), last := state.last.copy(),
                     fired := state.fired.copy(),
                     disp := state.dispatches.copy(),
                     stalled := state.stalled.copy()):
            host[slot_ids] = 0
        cap = state.cap.copy()
        for b, c in zip(slot_ids, caps):
            if c is not None and int(c) < 1:
                raise ValueError(f"slot {b}: cap must be >= 1, got {c}")
            cap[b] = self.max_cycles if c is None else int(c)
        quiesced = state.quiesced.copy()
        active[slot_ids] = 1
        quiesced[slot_ids] = False
        prof, prof_cycles = state.prof, state.prof_cycles
        chprof = state.mf["chprof"]
        if self.profile:
            m = jnp.asarray(mask)
            prof = _mf_prof_reset(prof, m)
            chprof = _mf_prof_reset(chprof, m)
            prof_cycles = prof_cycles.copy()
            prof_cycles[slot_ids] = 0
        return SlotState(
            fv_, fl_, full, val, ptr, out_last, out_count,
            active, base, last, fired, quiesced, disp,
            cap=cap, stalled=stalled,
            active_dev=jnp.asarray(
                np.broadcast_to(active[None], (self.P, B)).copy()),
            prof=prof, prof_cycles=prof_cycles,
            mf=dict(chf=chf, chv=chv, chprof=chprof))

    def slot_step(self, state: SlotState, nb: int) -> SlotState:
        prof_args = ((*state.prof, *state.mf["chprof"])
                     if self.profile else ())
        out = self._step(nb)(self._tabs, state.fv, state.fl, state.full,
                             state.val, state.ptr, state.out_last,
                             state.out_count, state.mf["chf"],
                             state.mf["chv"], state.active_dev,
                             *prof_args)
        full, val, ptr, out_last, out_count, chf, chv, f, lp = out[:9]
        prof = tuple(out[9:14]) if self.profile else None
        chprof = tuple(out[14:17]) if self.profile else None
        f, lp = jax.device_get((f, lp))
        f = np.asarray(f).sum(axis=0)
        lp = np.asarray(lp)[0]
        fired = state.fired + f
        last = np.where(lp > 0, state.base + lp, state.last)
        base = state.base + np.where(state.active > 0, nb, 0)
        quiesced = np.where(state.active > 0, lp < nb, state.quiesced)
        disp = state.dispatches + (state.active > 0)
        stalled = np.where(state.active > 0,
                           np.where(lp > 0, 0, state.stalled + 1),
                           state.stalled)
        prof_cycles = state.prof_cycles
        if self.profile and prof_cycles is not None:
            prof_cycles = prof_cycles + np.where(state.active > 0, nb, 0)
        return SlotState(state.fv, state.fl, full, val, ptr, out_last,
                         out_count, state.active.copy(), base, last,
                         fired, quiesced, disp, cap=state.cap,
                         stalled=stalled, active_dev=state.active_dev,
                         prof=prof, prof_cycles=prof_cycles,
                         mf=dict(chf=chf, chv=chv, chprof=chprof))

    def slot_harvest(self, state: SlotState, slot_ids
                     ) -> tuple[SlotState, list[EngineResult]]:
        slot_ids = list(slot_ids)
        idle = [b for b in slot_ids if not state.active[b]]
        if idle:
            raise ValueError(f"slots {idle} are free — nothing to harvest")
        out_last, out_count = jax.device_get((state.out_last,
                                              state.out_count))
        hprof = hch = None
        if self.profile and state.prof is not None:
            hprof = jax.device_get(state.prof)
            hch = jax.device_get(state.mf["chprof"])
        results = []
        for b in slot_ids:
            pr = nfires = None
            if hprof is not None:
                pr = self.merged_profile(
                    [x[:, b] for x in hprof],
                    [x[0, b, :self.C] for x in hch],
                    cycles=int(state.prof_cycles[b]),
                    dispatches=int(state.dispatches[b]))
                nfires = pr.node_fires
            results.append(EngineResult(
                outputs={a: out_last[r][b][k]
                         for a, r, k in self.out_rows},
                counts={a: int(out_count[r][b][k])
                        for a, r, k in self.out_rows},
                cycles=int(min(state.last[b] + 1, state.cap[b])),
                fired=int(state.fired[b]),
                dispatches=int(state.dispatches[b]),
                node_fires=nfires, profile=pr))
        active = state.active.copy()
        quiesced = state.quiesced.copy()
        active[slot_ids] = 0
        quiesced[slot_ids] = False
        return dataclasses.replace(
            state, active=active, quiesced=quiesced,
            active_dev=jnp.asarray(
                np.broadcast_to(active[None],
                                (self.P, state.slots)).copy())), results
