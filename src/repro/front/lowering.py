"""jaxpr-equation -> fabric-operator lowering rules (DESIGN.md §9).

One rule per jaxpr primitive.  The arithmetic/logic/relational
primitives map 1:1 onto :class:`~repro.core.graph.Op`; everything else
is a *schema* over several operators:

* fan-out — an arc carries one receiver, so a value consumed by k
  equations becomes a COPY tree (``library._fanout``);
* ``select_n`` (``jnp.where`` / ``lax.select``) — the classical
  dataflow conditional: each data operand rides a BRANCH steered by the
  predicate (the untaken side is SINKed) and a DMERGE reunites the
  taken tokens, so *both* operands are consumed every firing and the
  fabric streams without stale tokens;
* ``neg`` / ``abs`` / ``integer_pow`` / ``clamp`` — expanded into
  SUB/MUL/MAX/MIN trees that are bit-exact at the execution dtype
  (``neg`` is ``0 - x`` for ints, ``x * -1`` for floats, so ``-0.0``
  and INT_MIN behave exactly like jax);
* constants — jaxpr literals and closure consts become sticky const
  buses (always-full environment arcs), which is what lets the PR 3
  constant-folding pass collapse constant subexpressions at compile
  time;
* ``jit`` / ``pjit`` / ``custom_jvp_call`` etc. — inlined recursively;
* ``while`` / carry-only ``scan`` (``lax.while_loop``, ``fori_loop``,
  carry-only ``lax.scan``) — the paper's cyclic loop schema
  (DESIGN.md §10): an NDMERGE entry per carry whose initial value
  arrives as a one-shot token (an initial-token annotation for
  compile-time values, the carry's supply arc otherwise), a predicate
  cone over per-iteration carry taps, and a BRANCH per carry steering
  the token onto the back-edge (predicate true) or the exit arc
  (false).  Loop-invariant values that are sticky const buses ride
  straight into the cones; streamy invariants become synthetic
  pass-through carries.  The resulting fabric is cyclic, so it runs on
  token-presence executors only, and it initiates ONCE per program
  run — ``TracedProgram.make_feeds`` enforces one token per argument.

Anything else raises :class:`LoweringError` naming the primitive.
"""
from __future__ import annotations

import itertools

import numpy as np

from repro.core.graph import Graph, Op
from repro.core.library import _fanout, _reduce_tree


class LoweringError(Exception):
    """A traced program contains an equation the fabric cannot run."""


# primitive name -> Op / schema note (the DESIGN.md §9 lowering table;
# also the vocabulary quoted by LoweringError messages)
SUPPORTED = {
    "add": "ADD", "sub": "SUB", "mul": "MUL",
    "div": "DIV (float dtypes only; the fabric ALU defines x/0 = 0)",
    "max": "MAX", "min": "MIN",
    "and": "AND", "or": "OR", "xor": "XOR", "not": "NOT",
    "shift_left": "SHL",
    "shift_right_arithmetic": "SHR (signed dtypes)",
    "shift_right_logical": "SHR (unsigned dtypes)",
    "gt": "IFGT", "ge": "IFGE", "lt": "IFLT", "le": "IFLE",
    "eq": "IFEQ", "ne": "IFDF",
    "select_n": "BRANCH x2 + SINK x2 + DMERGE (2-way, bool predicate)",
    "neg": "SUB(0, x) int / MUL(x, -1) float",
    "abs": "COPY + neg + MAX",
    "integer_pow": "MUL tree (int dtypes, y >= 0)",
    "clamp": "MAX + MIN",
    "convert_element_type": "alias (bool->dtype / same dtype) or "
                            "IFDF(x, 0) (dtype->bool)",
    "stop_gradient": "alias",
    "broadcast_in_dim": "alias (scalar)", "reshape": "alias (scalar)",
    "squeeze": "alias (scalar)",
    "jit": "inlined", "pjit": "inlined", "closed_call": "inlined",
    "custom_jvp_call": "inlined", "custom_vjp_call": "inlined",
    "while": "cyclic loop schema: NDMERGE entry per carry + predicate "
             "cone + BRANCH back-edge/exit steering (scalar carries)",
    "scan": "carry-only (fori_loop with static bounds): counter carry "
            "+ IFLT trip decider + the while loop schema",
}

_BINOP = {
    "add": Op.ADD, "sub": Op.SUB, "mul": Op.MUL,
    "max": Op.MAX, "min": Op.MIN,
    "and": Op.AND, "or": Op.OR, "xor": Op.XOR,
    "shift_left": Op.SHL,
    "gt": Op.IFGT, "ge": Op.IFGE, "lt": Op.IFLT, "le": Op.IFLE,
    "eq": Op.IFEQ, "ne": Op.IFDF,
}
# `a op b == b op a` bit-exactly at any dtype (engine ALU formulas):
# used to put a const operand on the b side, where the identity-
# elimination pass looks for it.
_COMMUTATIVE = frozenset(
    ("add", "mul", "max", "min", "and", "or", "xor", "eq", "ne"))
_ALIAS = ("stop_gradient", "broadcast_in_dim", "reshape", "squeeze")
_CALL = ("jit", "pjit", "closed_call", "custom_jvp_call",
         "custom_vjp_call")


def _is_literal(atom) -> bool:
    return not hasattr(atom, "count")    # jax core Var has .count


class _Ctx:
    """Lowering state: per-var arc supplies, use counts, taint."""

    def __init__(self, graph: Graph, dtype):
        self.graph = graph
        self.dtype = np.dtype(dtype)
        self.supply: dict = {}     # Var -> list[str] queue | str const arc
        self.uses: dict = {}       # Var -> planned consumer count
        self.streamy: dict = {}    # Var -> depends on an env stream?
        self.env_inputs: set[str] = set()
        self.const_args: dict[int, object] = {}   # arg index -> value
        self.has_loops = False     # a while/scan lowered a cyclic region
        self.loop_depth = 0        # loop-body nesting during lowering
        self._n = itertools.count()
        self._lits: dict = {}

    def fresh(self, tag: str = "v") -> str:
        return f"{tag}{next(self._n)}"

    # -- constants ------------------------------------------------------
    def lit(self, value) -> str:
        """Const bus for a compile-time scalar (deduped by value bits —
        const arcs are sticky and may feed many receivers)."""
        v = np.asarray(value, self.dtype).reshape(()).item()
        key = repr(v)
        arc = self._lits.get(key)
        if arc is None:
            arc = self.fresh("lit")
            self.graph.const(arc, v)
            self._lits[key] = arc
        return arc

    # -- supplies -------------------------------------------------------
    def use(self, atom) -> str:
        """Claim one arc carrying the atom's value."""
        if _is_literal(atom):
            return self.lit(atom.val)
        s = self.supply[atom]
        return s if isinstance(s, str) else s.pop(0)

    def is_streamy(self, atom) -> bool:
        return (not _is_literal(atom)) and self.streamy.get(atom, False)

    def bind(self, var, arc: str, streamy: bool = True) -> None:
        """Register `arc` as var's value, fanning out through a COPY
        tree when the var has several consumers and SINKing it when it
        has none (a produced token must always find a receiver, or the
        arc would surface as a spurious environment output)."""
        u = self.uses.get(var, 0)
        if u == 0:
            self.graph.add(Op.SINK, [arc], [])
            self.supply[var] = []
        elif u == 1:
            self.supply[var] = [arc]
        else:
            self.supply[var] = _fanout(self.graph, arc, u, arc + "f")
        self.streamy[var] = streamy

    def bind_const(self, var, arc: str) -> None:
        self.supply[var] = arc      # sticky bus: unlimited receivers
        self.streamy[var] = False


def _err(eqn, why: str) -> LoweringError:
    return LoweringError(
        f"primitive '{eqn.primitive.name}' {why} "
        f"(fabric lowering table: {sorted(SUPPORTED)})")


def _aval_dtype(atom):
    return np.dtype(atom.aval.dtype) if not _is_literal(atom) \
        else np.dtype(np.asarray(atom.val).dtype)


def _convert_kind(ctx: _Ctx, eqn) -> str:
    """alias | ne0 — or raise for a conversion the fabric cannot carry
    (arcs hold one dtype; deciders already emit 0/1 at that dtype)."""
    src = _aval_dtype(eqn.invars[0])
    dst = np.dtype(eqn.params["new_dtype"])
    if src == dst or (src == np.bool_ and dst == ctx.dtype):
        return "alias"
    if dst == np.bool_ and src == ctx.dtype:
        return "ne0"
    raise _err(eqn, f"converts {src} -> {dst}, but every arc of this "
                    f"fabric carries {ctx.dtype} tokens")


def _pow_uses(eqn, uses) -> int:
    y = int(eqn.params["y"])
    if y == 1:
        return uses.get(eqn.outvars[0], 0)      # pure alias
    return max(y, 0)


def _multiplicities(ctx: _Ctx, eqn, uses) -> list[int]:
    """How many arcs of each operand the eqn's lowering will claim.
    ``uses`` holds the (already complete, thanks to reverse iteration)
    consumer counts of the eqn's outvars — alias lowerings forward
    their output's demand straight to their input."""
    name = eqn.primitive.name
    if name == "select_n":
        return [3] + [1] * (len(eqn.invars) - 1)
    if name == "abs":
        return [2]
    if name == "integer_pow":
        return [_pow_uses(eqn, uses)]
    if name in _ALIAS:
        return [uses.get(eqn.outvars[0], 0)]
    if name == "convert_element_type" and _convert_kind(ctx, eqn) == "alias":
        return [uses.get(eqn.outvars[0], 0)]
    return [1] * len(eqn.invars)


def _bind_alias(ctx: _Ctx, outvar, atom) -> None:
    """outvar shares atom's arcs (its demand was pre-charged to atom)."""
    if _is_literal(atom):
        ctx.bind_const(outvar, ctx.lit(atom.val))
        return
    s = ctx.supply[atom]
    if isinstance(s, str):
        ctx.bind_const(outvar, s)
    else:
        arcs = [ctx.use(atom) for _ in range(ctx.uses.get(outvar, 0))]
        ctx.supply[outvar] = arcs
        ctx.streamy[outvar] = ctx.is_streamy(atom)


# ---------------------------------------------------------------------------
# Loop lowering: lax control flow -> the paper's cyclic loop schema
# ---------------------------------------------------------------------------
def _check_scalar_loop(eqn) -> None:
    for v in (*eqn.invars, *eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and tuple(aval.shape) != ():
            raise _err(eqn, f"carries a value of shape "
                            f"{tuple(aval.shape)}; fabric loops carry "
                            "scalar tokens")


def _one_shot_init(ctx: _Ctx, arc: str, streamy: bool, eqn) -> str:
    """Entry-NDMERGE initial-value input: must deliver exactly one
    token per loop INITIATION (a second arrival would re-initiate a
    live loop).  A top-level const-bus supply becomes a fresh
    init-annotated arc (the one-shot compile-time initial token of
    DESIGN.md §10); a streamy supply arc carries one token per
    initiation itself.  Nested const inits never reach here — the
    caller materializes them per initiation first.  A non-streamy
    non-const supply is produced by a free-running const-fed operator
    and is rejected."""
    g = ctx.graph
    if arc in g.consts:
        f = ctx.fresh("lz")
        g.init(f, np.asarray(g.consts[arc], ctx.dtype).reshape(()).item())
        return f
    if not streamy:
        raise _err(eqn, "has a loop initial value produced by a "
                        "free-running const-fed operator; hoist it to a "
                        "literal or derive it from an argument")
    return arc


def _loop_schema(ctx: _Ctx, eqn, *, init_sup, inv_entries, need_tap,
                 make_pred, make_backs) -> list[str]:
    """Build the paper's cyclic loop schema; returns the exit arcs.

    init_sup     ``[(arc, streamy)]`` initial-value supply per carry.
    inv_entries  ``[(bind, arc, streamy, where)]`` — loop-invariant
                 values that are NOT sticky const buses; each becomes a
                 *synthetic pass-through carry* (entry merge + tap +
                 BRANCH whose exit token is SINKed) and ``bind(tap)``
                 hands its per-iteration tap arc to the consuming cone.
                 ``where`` is the cone that consumes the tap: a
                 ``"cond"`` invariant is tapped BEFORE its BRANCH (the
                 predicate fires once more than the body — the final,
                 false evaluation still reads it), a ``"body"``
                 invariant AFTER (the tap must exist only on continuing
                 iterations, or a stale token per initiation would
                 poison re-initiating nested loops).
    need_tap[j]  carry j feeds the predicate cone (gets a COPY tap);
                 untapped carries wire straight into their BRANCH.
    make_pred(taps) -> (p_arc, p_streamy): lower the predicate cone
                 (``taps[j]`` is None when ``need_tap[j]`` is False).
    make_backs(live) -> ``[(arc, streamy)]``: lower the body cone from
                 the BRANCH-true arcs; one next-state arc per carry.

    Wiring per carry (DESIGN.md §10)::

            back ----v
        NDMERGE(back, init) -> carry -> COPY -> (tap, data)
            tap  -> predicate cone -> p (fanned out)
            data -> BRANCH(data, p) -> (live -> body -> back,  exit)

    The entry NDMERGE is race-free by construction: its init input
    delivers exactly one token per run and every later token arrives on
    the back edge, serialized by the cycle itself.
    """
    g = ctx.graph
    n = len(init_sup)
    s = len(inv_entries)
    # NESTED loops re-initiate once per enclosing iteration, and the
    # enclosing body's carries advance at skewed rates (a carry whose
    # cycle contains this loop iterates slower than one that does not),
    # so a fresh initiation token can arrive while the previous
    # initiation's back-edge token is still in flight — an NDMERGE
    # entry would race.  Nested loops therefore use the classical
    # DETERMINISTIC entry instead: a DMERGE steered by the loop
    # predicate carrying an initial-0 control token (sel=0 -> take the
    # init input, sel=p=1 -> take the back edge, and the exit firing's
    # p=0 becomes the NEXT initiation's sel) — re-initiation-safe by
    # construction, and const initial values ride their sticky buses
    # straight into the merge.  Top-level loops initiate exactly once
    # (make_feeds enforces the single-shot contract), so they keep the
    # paper's NDMERGE schema with one-shot initial tokens.
    nested = ctx.loop_depth > 0
    ctx.loop_depth += 1
    # entry-merge output arcs are allocated NOW; the NDMERGE nodes are
    # added LAST (their back-edge inputs only exist after the body cone
    # lowers) — node order in the table does not affect semantics
    carry = [ctx.fresh("lc") for _ in range(n)]
    inv = [ctx.fresh("li") for _ in range(s)]
    taps, data = [], []
    for j, a in enumerate(carry):
        if need_tap[j]:
            t, d = ctx.fresh(), ctx.fresh()
            g.add(Op.COPY, [a], [t, d])
        else:
            t, d = None, a
        taps.append(t)
        data.append(d)
    for (bind, _, _, where), a in zip(inv_entries, inv):
        if where == "cond":     # tap pre-BRANCH: T+1 per initiation
            t, d = ctx.fresh(), ctx.fresh()
            g.add(Op.COPY, [a], [t, d])
            bind(t)
            data.append(d)
        else:                   # tap post-BRANCH (below): T per init
            data.append(a)
    p_arc, p_streamy = make_pred(taps)
    if p_arc in g.consts or not p_streamy:
        raise _err(eqn, "has a loop predicate that does not depend on "
                        "the loop state — the trip count would be zero "
                        "or infinite at compile time")
    # the BRANCH nodes are added AFTER the body cone lowers — their
    # predicate-leg count depends on whether a predicate-derived gate
    # is needed (below), and the body only needs the live arc NAMES
    m = n + s
    live = [ctx.fresh("ll") for _ in range(n)]
    exits = [ctx.fresh("lx") for _ in range(n)]
    synth_live = [ctx.fresh("lv") for _ in range(s)]
    synth_backs = []
    for j, (bind, _, _, where) in enumerate(inv_entries):
        if where == "cond":
            synth_backs.append(synth_live[j])
        else:                           # body tap rides the live token
            t, back = ctx.fresh(), ctx.fresh()
            g.add(Op.COPY, [synth_live[j]], [t, back])
            bind(t)
            synth_backs.append(back)
    backs = list(make_backs(live))
    ctx.loop_depth -= 1
    # next-state fixup: a constant next value (body returns a literal /
    # const pass-through) has no per-iteration producer, and wiring the
    # always-full const bus into a top-level NDMERGE entry would
    # re-fire it every refill window.  Gate one token per CONTINUING
    # iteration instead: DMERGE with both data inputs riding the const
    # bus and the gate token as control produces exactly one
    # const-valued token per body firing.  The gate rides a streamy
    # back value when one exists, else an extra predicate token routed
    # by its own twin (BRANCH(p, p): the true output exists only on
    # continuing iterations) — a loop whose EVERY next state is
    # constant is still data-dependent through its zero-trip path.
    # The nested DMERGE entry consumes its chosen bus per firing, so
    # const backs ride their sticky buses directly there.
    const_j = [j for j, (a, _) in enumerate(backs) if a in g.consts]
    free_j = [j for j, (a, sy) in enumerate(backs)
              if a not in g.consts and not sy]
    if free_j:
        raise _err(eqn, "has a loop next-state value produced by a "
                        "free-running const-fed operator — its arc "
                        "would re-initiate the loop; hoist it to a "
                        "literal or derive it from the carry")
    need_gates = bool(const_j) and not nested
    gate_j = next((j for j, (a, sy) in enumerate(backs)
                   if a not in g.consts and sy), None) if need_gates \
        else None
    p_gate = need_gates and gate_j is None
    # nested entries consume the predicate too (as the DMERGE steering
    # stream): double the fan-out and pre-load each steering leg with
    # the initial-0 token that selects the first initiation's input
    ps = _fanout(g, p_arc, (2 * m if nested else m)
                 + (2 if p_gate else 0), p_arc + "f")
    sels = ps[m:2 * m] if nested else []
    for a in sels:
        g.init(a, 0)
    for j in range(n):
        g.add(Op.BRANCH, [data[j], ps[j]], [live[j], exits[j]])
    for j in range(s):
        ex = ctx.fresh()
        g.add(Op.BRANCH, [data[n + j], ps[n + j]], [synth_live[j], ex])
        g.add(Op.SINK, [ex], [])        # invariant's exit value is dead
    if need_gates:
        if p_gate:
            gl, gd = ctx.fresh("lgl"), ctx.fresh()
            g.add(Op.BRANCH, [ps[-2], ps[-1]], [gl, gd])
            g.add(Op.SINK, [gd], [])    # the final (false) evaluation
            gates = _fanout(g, gl, len(const_j), ctx.fresh("lg"))
        else:
            fan = _fanout(g, backs[gate_j][0], 1 + len(const_j),
                          ctx.fresh("lg"))
            backs[gate_j] = (fan[0], True)
            gates = fan[1:]
        for gate, j in zip(gates, const_j):
            out = ctx.fresh("lk")
            g.add(Op.DMERGE, [backs[j][0], backs[j][0], gate], [out])
            backs[j] = (out, True)
    # close the cycles: one entry merge per carry — the paper's NDMERGE
    # at top level, the predicate-steered deterministic DMERGE nested
    all_backs = [b for b, _ in backs] + synth_backs
    all_inits = list(init_sup) + [(a, sy) for _, a, sy, _ in inv_entries]
    all_carry = carry + inv
    for j in range(m):
        back, (ini_arc, ini_sy) = all_backs[j], all_inits[j]
        if nested:
            if ini_arc not in g.consts and not ini_sy:
                raise _err(eqn, "has a loop initial value produced by "
                                "a free-running const-fed operator; "
                                "hoist it to a literal or derive it "
                                "from an argument")
            g.add(Op.DMERGE, [back, ini_arc, sels[j]], [all_carry[j]])
        else:
            ini = _one_shot_init(ctx, ini_arc, ini_sy, eqn)
            g.add(Op.NDMERGE, [back, ini], [all_carry[j]])
    ctx.has_loops = True
    return exits


def _split_invariants(ctx: _Ctx, sup, out, where: str):
    """Partition loop-invariant supplies: sticky const buses ride into
    the cone directly (``out[k]`` set now); anything else registers a
    synthetic carry whose ``bind`` fills ``out[k]`` with the tap arc.
    ``where`` names the consuming cone ("cond" | "body") — it decides
    the tap cadence (see :func:`_loop_schema`)."""
    inv_entries = []
    for k, (arc, sy) in enumerate(sup):
        if arc in ctx.graph.consts:
            out[k] = (arc, False)
        else:
            def bind(t, k=k, out=out):
                out[k] = (t, True)
            inv_entries.append((bind, arc, sy, where))
    return inv_entries


def _lower_while(ctx: _Ctx, eqn) -> None:
    _check_scalar_loop(eqn)
    cond_cj = eqn.params["cond_jaxpr"]
    body_cj = eqn.params["body_jaxpr"]
    nc = eqn.params["cond_nconsts"]
    nb = eqn.params["body_nconsts"]
    n = len(eqn.invars) - nc - nb
    sup = [(ctx.use(v), ctx.is_streamy(v)) for v in eqn.invars]
    cond_in = [None] * nc
    body_in = [None] * nb
    inv_entries = (_split_invariants(ctx, sup[:nc], cond_in, "cond")
                   + _split_invariants(ctx, sup[nc:nc + nb], body_in,
                                       "body"))

    def make_pred(taps):
        res = lower_jaxpr(ctx, cond_cj.jaxpr, cond_cj.consts,
                          cond_in + [(t, True) for t in taps])
        return res[0]

    def make_backs(live):
        return lower_jaxpr(ctx, body_cj.jaxpr, body_cj.consts,
                           body_in + [(a, True) for a in live])

    exits = _loop_schema(ctx, eqn, init_sup=sup[nc + nb:],
                         inv_entries=inv_entries, need_tap=[True] * n,
                         make_pred=make_pred, make_backs=make_backs)
    for v, ex in zip(eqn.outvars, exits):
        ctx.bind(v, ex, streamy=True)


def _lower_scan(ctx: _Ctx, eqn) -> None:
    """Carry-only scan (what ``fori_loop`` with static bounds traces
    to): a synthetic counter carry and an ``IFLT(i, length)`` decider
    supply the predicate; the user carries ride the while schema with
    no predicate taps of their own.

    Note a fori-derived scan already carries the jax loop index, so
    such fabrics run two parallel counters (~5 extra nodes).  Reusing
    the existing one is a possible peephole, but it requires proving
    carry 0 is ``init==lo, +1 per step`` against arbitrary bounds —
    left as a simplification opportunity."""
    p = eqn.params
    num_consts, num_carry = p["num_consts"], p["num_carry"]
    n_xs = len(eqn.invars) - num_consts - num_carry
    n_ys = len(eqn.outvars) - num_carry
    if n_xs or n_ys:
        raise _err(eqn, f"scans over {n_xs} streamed input / {n_ys} "
                        "streamed output axes; only carry-only scans "
                        "(e.g. fori_loop with static bounds) ride the "
                        "loop schema")
    _check_scalar_loop(eqn)
    g = ctx.graph
    body_cj = p["jaxpr"]
    sup = [(ctx.use(v), ctx.is_streamy(v)) for v in eqn.invars]
    body_in = [None] * num_consts
    inv_entries = _split_invariants(ctx, sup[:num_consts], body_in,
                                    "body")
    len_bus = ctx.lit(int(p["length"]))
    one_bus = ctx.lit(1)

    def make_pred(taps):
        pa = ctx.fresh("lp")
        g.add(Op.IFLT, [taps[0], len_bus], [pa])
        return pa, True

    def make_backs(live):
        nxt = ctx.fresh("ln")
        g.add(Op.ADD, [live[0], one_bus], [nxt])
        res = lower_jaxpr(ctx, body_cj.jaxpr, body_cj.consts,
                          body_in + [(a, True) for a in live[1:]])
        return [(nxt, True)] + list(res)

    exits = _loop_schema(
        ctx, eqn, init_sup=[(ctx.lit(0), False)] + sup[num_consts:],
        inv_entries=inv_entries,
        need_tap=[True] + [False] * num_carry,
        make_pred=make_pred, make_backs=make_backs)
    g.add(Op.SINK, [exits[0]], [])      # final counter value is dead
    for v, ex in zip(eqn.outvars, exits[1:]):
        ctx.bind(v, ex, streamy=True)


def _lower_eqn(ctx: _Ctx, eqn) -> None:
    name = eqn.primitive.name
    g, dtype = ctx.graph, ctx.dtype
    is_int = np.issubdtype(dtype, np.integer)
    out = eqn.outvars[0] if eqn.outvars else None

    if name in _BINOP or name == "div" or name.startswith("shift_right"):
        if name == "div":
            if is_int:
                raise _err(eqn, "is round-toward-zero integer division "
                                "(jnp `//` also routes through `rem`); "
                                "the fabric DIV is float-only — use "
                                "shifts for powers of two")
            op = Op.DIV
        elif name == "shift_right_arithmetic":
            if not is_int or np.issubdtype(dtype, np.unsignedinteger):
                raise _err(eqn, "needs a signed integer dtype")
            op = Op.SHR
        elif name == "shift_right_logical":
            if not np.issubdtype(dtype, np.unsignedinteger):
                raise _err(eqn, "is a logical shift; the fabric SHR is "
                                "arithmetic for signed dtypes — use an "
                                "unsigned dtype")
            op = Op.SHR
        else:
            op = _BINOP[name]
        a, b = eqn.invars
        if (name in _COMMUTATIVE and not ctx.is_streamy(a)
                and ctx.is_streamy(b)):
            a, b = b, a          # const operand on the b side (passes
            #                      splice identities off inputs[1])
        streamy = ctx.is_streamy(a) or ctx.is_streamy(b)
        arc = ctx.fresh()
        g.add(op, [ctx.use(a), ctx.use(b)], [arc])
        ctx.bind(out, arc, streamy)
        return

    if name == "not":
        arc = ctx.fresh()
        g.add(Op.NOT, [ctx.use(eqn.invars[0])], [arc])
        ctx.bind(out, arc, ctx.is_streamy(eqn.invars[0]))
        return

    if name == "neg":
        x = eqn.invars[0]
        arc = ctx.fresh()
        if is_int:
            g.add(Op.SUB, [ctx.lit(0), ctx.use(x)], [arc])
        else:           # 0.0 - x flips -0.0; x * -1.0 is bit-exact
            g.add(Op.MUL, [ctx.use(x), ctx.lit(-1)], [arc])
        ctx.bind(out, arc, ctx.is_streamy(x))
        return

    if name == "abs":
        x = eqn.invars[0]
        x0, x1 = ctx.use(x), ctx.use(x)
        nn = ctx.fresh()
        if is_int:
            g.add(Op.SUB, [ctx.lit(0), x1], [nn])
        else:
            g.add(Op.MUL, [x1, ctx.lit(-1)], [nn])
        arc = ctx.fresh()
        g.add(Op.MAX, [x0, nn], [arc])    # MAX(+0,-0)=+0 matches |−0.0|
        ctx.bind(out, arc, ctx.is_streamy(x))
        return

    if name == "integer_pow":
        x = eqn.invars[0]
        y = int(eqn.params["y"])
        if y < 0:
            raise _err(eqn, f"has negative exponent y={y}")
        if y == 0:
            ctx.bind_const(out, ctx.lit(1))
            return
        if y == 1:
            _bind_alias(ctx, out, x)
            return
        if not is_int:
            raise _err(eqn, "expands to a MUL tree whose rounding "
                            "order is only bit-exact for integer "
                            "dtypes — spell out float powers as "
                            "explicit multiplies")
        arcs = [ctx.use(x) for _ in range(y)]
        arc = ctx.fresh()
        _reduce_tree(g, arcs, Op.MUL, arc + "p", final=arc)
        ctx.bind(out, arc, ctx.is_streamy(x))
        return

    if name == "clamp":
        lo, x, hi = eqn.invars    # lax.clamp(min, operand, max)
        t, arc = ctx.fresh(), ctx.fresh()
        g.add(Op.MAX, [ctx.use(x), ctx.use(lo)], [t])
        g.add(Op.MIN, [t, ctx.use(hi)], [arc])
        ctx.bind(out, arc, any(ctx.is_streamy(v) for v in eqn.invars))
        return

    if name == "select_n":
        pred = eqn.invars[0]
        if len(eqn.invars) != 3:
            raise _err(eqn, f"has {len(eqn.invars) - 1} cases; only "
                            "2-way (boolean) selects lower")
        if _aval_dtype(pred) != np.bool_:
            raise _err(eqn, "has a non-boolean selector")
        fv, tv = eqn.invars[1], eqn.invars[2]   # select_n: cases[pred]
        c_t, c_f, c_m = ctx.use(pred), ctx.use(pred), ctx.use(pred)
        t_live, t_dead = ctx.fresh(), ctx.fresh()
        f_live, f_dead = ctx.fresh(), ctx.fresh()
        g.add(Op.BRANCH, [ctx.use(tv), c_t], [t_live, t_dead])
        g.add(Op.SINK, [t_dead], [])
        g.add(Op.BRANCH, [ctx.use(fv), c_f], [f_dead, f_live])
        g.add(Op.SINK, [f_dead], [])
        arc = ctx.fresh()
        g.add(Op.DMERGE, [t_live, f_live, c_m], [arc])
        ctx.bind(out, arc, any(ctx.is_streamy(v) for v in eqn.invars))
        return

    if name == "convert_element_type":
        x = eqn.invars[0]
        if _convert_kind(ctx, eqn) == "alias":
            _bind_alias(ctx, out, x)
        else:                     # dtype -> bool: x != 0
            arc = ctx.fresh()
            g.add(Op.IFDF, [ctx.use(x), ctx.lit(0)], [arc])
            ctx.bind(out, arc, ctx.is_streamy(x))
        return

    if name in _ALIAS:
        aval = getattr(eqn.outvars[0], "aval", None)
        if aval is not None and tuple(aval.shape) != ():
            raise _err(eqn, f"produces shape {tuple(aval.shape)}; the "
                            "fabric carries scalar tokens")
        _bind_alias(ctx, out, eqn.invars[0])
        return

    if name == "while":
        _lower_while(ctx, eqn)
        return

    if name == "scan":
        _lower_scan(ctx, eqn)
        return

    if name in _CALL:
        inner = None
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            cand = eqn.params.get(key)
            if cand is not None and hasattr(cand, "jaxpr"):
                inner = cand
                break
        if inner is None:
            raise _err(eqn, "has no inlinable sub-jaxpr")
        supplies = [(ctx.use(v), ctx.is_streamy(v)) for v in eqn.invars]
        results = lower_jaxpr(ctx, inner.jaxpr, inner.consts, supplies)
        for var, (arc, streamy) in zip(eqn.outvars, results):
            if arc in ctx.graph.consts:
                ctx.bind_const(var, arc)
            else:
                ctx.bind(var, arc, streamy)
        return

    raise _err(eqn, "has no fabric lowering")


def lower_jaxpr(ctx: _Ctx, jaxpr, const_vals, in_arcs
                ) -> list[tuple[str, bool]]:
    """Lower one jaxpr scope onto ctx.graph.

    in_arcs: one ``(arc, streamy)`` pair per invar — or None (top
    level) to create an environment input arc ``in{i}`` on demand,
    recording the created names (None for unused args) in
    ``ctx.created_inputs``.  Returns ``(arc, streamy)`` per outvar;
    unused invar arcs handed in by a caller are SINKed so every token
    still finds a receiver.
    """
    # 1. demand counting, in reverse so alias chains see their own
    #    consumers before charging their inputs
    uses: dict = {}

    def charge(atom, m):
        if not _is_literal(atom) and m:
            uses[atom] = uses.get(atom, 0) + m

    for v in jaxpr.outvars:
        charge(v, 1)
    for eqn in reversed(jaxpr.eqns):
        for atom, m in zip(eqn.invars, _multiplicities(ctx, eqn, uses)):
            charge(atom, m)
    ctx.uses.update(uses)

    # 2. bind closure consts and arguments
    for var, val in zip(jaxpr.constvars, const_vals):
        val = np.asarray(val)
        if val.shape != ():
            raise LoweringError(
                f"closure constant of shape {val.shape} cannot ride a "
                "scalar-token arc (fabric tokens are 0-d)")
        ctx.bind_const(var, ctx.lit(val))
    if in_arcs is None:                 # top level: environment streams
        created: list[str | None] = []
        for i, var in enumerate(jaxpr.invars):
            if i in ctx.const_args:     # sticky const bus, not a stream
                if ctx.uses.get(var, 0):
                    ctx.bind_const(var, ctx.lit(ctx.const_args[i]))
                continue
            if ctx.uses.get(var, 0) == 0:
                created.append(None)    # unused argument: no arc at all
                continue
            arc = f"in{i}"
            ctx.env_inputs.add(arc)
            created.append(arc)
            ctx.bind(var, arc, streamy=True)
        ctx.created_inputs = created
    else:                               # inlined call: arcs handed in
        for var, (arc, streamy) in zip(jaxpr.invars, in_arcs):
            if arc in ctx.graph.consts:
                ctx.bind_const(var, arc)
            else:
                ctx.bind(var, arc, streamy)

    # 3. equations in program order
    for eqn in jaxpr.eqns:
        _lower_eqn(ctx, eqn)

    # 4. outputs
    results = []
    for v in jaxpr.outvars:
        if _is_literal(v):
            results.append((ctx.lit(v.val), False))
        else:
            results.append((ctx.use(v), ctx.is_streamy(v)))
    return results
