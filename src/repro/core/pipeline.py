"""Pipeline parallelism scheduled BY the paper's dataflow engine.

The mapping (DESIGN.md §4): pipeline stages are dataflow operator nodes,
microbatches are tokens, the inter-stage activation transfer is the arc
(str/ack handshake -> ``lax.ppermute``), and the schedule is obtained by
*simulating the stage chain on the static dataflow engine itself* —
each stage fires when its input arc holds a token and its output arc is
empty.

Two schedules:

* ``dataflow`` (paper-faithful): the engine's one-token-per-arc handshake
  sustains one token per TWO cycles per arc (paper §3.1), giving a
  2M+S-1-step schedule — stages alternate work/idle exactly like the
  str/ack exchange in paper Fig. 3.
* ``dense`` (beyond-paper): double-buffered arcs (the clocked pipeline of
  Teifel's Fig. 1c, which the paper cites as its synchronous model)
  recover the classic M+S-1 GPipe wavefront.  The measured step-count
  ratio between the two is reported in §Perf.

Both schedules drive the same executor: a ``shard_map`` over the "pp"
mesh axis, ``lax.scan`` over schedule steps, ``ppermute`` stage-to-stage
handshakes.  Backward (autodiff through ppermute/scan) yields the reverse
pipeline automatically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.graph import Graph, Op
from repro.core.engine import run_reference


# ---------------------------------------------------------------------------
# schedule generation
# ---------------------------------------------------------------------------
def stage_chain_graph(n_stages: int) -> Graph:
    """The pipeline as a dataflow fabric: a chain of operator nodes."""
    g = Graph(name=f"pipeline_{n_stages}")
    g.const("zero", 0)
    arcs = ["mb_in"] + [f"a{i}" for i in range(1, n_stages)] + ["mb_out"]
    for s in range(n_stages):
        # identity operator (OR with 0) so the traced token value is the
        # microbatch id itself
        g.add(Op.OR, [arcs[s], "zero"], [arcs[s + 1]], name=f"stage{s}")
    return g


def dataflow_schedule(n_stages: int, n_micro: int) -> np.ndarray:
    """Schedule table [T, S] (microbatch index or -1) simulated on the
    static dataflow engine (paper-faithful one-token-per-arc)."""
    g = stage_chain_graph(n_stages)
    events = []
    run_reference(g, {"mb_in": np.arange(n_micro)},
                  trace=events.append)
    # events: (cycle, node_index, microbatch_value)
    T = max(c for c, _, _ in events)
    table = np.full((T, n_stages), -1, np.int32)
    for cycle, node, val in events:
        table[cycle - 1, node] = val
    return table


def dense_schedule(n_stages: int, n_micro: int) -> np.ndarray:
    """Double-buffered-arc schedule: classic M+S-1 wavefront."""
    T = n_micro + n_stages - 1
    table = np.full((T, n_stages), -1, np.int32)
    for t in range(T):
        for s in range(n_stages):
            m = t - s
            if 0 <= m < n_micro:
                table[t, s] = m
    return table


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------
def pipeline_apply(mesh: Mesh, stage_fn, stage_params, x_micro,
                   schedule: np.ndarray):
    """Run the pipelined stack.

    stage_fn: (local_params, x [mb, ...]) -> y [mb, ...]
    stage_params: pytree with leading layer axis, sharded over "pp"
    x_micro: [M, mb, ...] microbatched input (replicated)
    schedule: [T, S] static table.
    Returns y_micro [M, mb, ...].
    """
    # jax.make_mesh gives Explicit axes; the stage loop (and its
    # transpose under grad) is written for Auto ones
    mesh = Mesh(mesh.devices, mesh.axis_names)
    S = mesh.shape["pp"]
    T, S2 = schedule.shape
    assert S2 == S, (schedule.shape, S)
    M = x_micro.shape[0]
    sched = jnp.asarray(schedule)
    perm = [(i, i + 1) for i in range(S - 1)]

    def per_stage(params_local, x_all):
        stage = jax.lax.axis_index("pp")
        mb_shape = x_all.shape[1:]
        recv = jnp.zeros(mb_shape, x_all.dtype)
        out = jnp.zeros_like(x_all)

        def step(carry, sched_row):
            recv, out = carry
            mb = sched_row[stage]
            active = mb >= 0
            inp = jnp.where(stage == 0,
                            x_all[jnp.clip(mb, 0, M - 1)], recv)

            def work(x):
                return stage_fn(params_local, x)

            y = jax.lax.cond(active, work, lambda x: x, inp)
            # last stage deposits its finished microbatch
            out = jnp.where(
                (stage == S - 1) & active,
                jax.lax.dynamic_update_index_in_dim(
                    out, y, jnp.clip(mb, 0, M - 1), 0),
                out)
            # handshake: send to the right neighbour
            send = jax.lax.ppermute(y, "pp", perm)
            return (send, out), None

        (_, out), _ = jax.lax.scan(step, (recv, out), sched)
        # only the last stage's `out` is real; broadcast it to all stages
        # (masked psum) so the out_spec can be replicated
        out = jax.lax.psum(
            jnp.where(stage == S - 1, out, jnp.zeros_like(out)), "pp")
        return out

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P("pp"), stage_params), P()),
        out_specs=P(), check_vma=False)
    return fn(stage_params, x_micro)


def make_stage_fn(cfg, n_local_layers: int):
    """Default stage: scan of dense transformer layers (repro.models)."""
    from repro.models.transformer import _dense_body

    def stage_fn(params_local, x):
        pos = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])

        def body(x, lp):
            x, _ = _dense_body(cfg, lp, x, pos)
            return x, None

        y, _ = jax.lax.scan(body, x, params_local)
        return y

    return stage_fn
