"""Frozen cycle-accurate oracle of the static dataflow machine.

The benchmark's own copy of the machine's semantics (arXiv:1110.3655,
Veen's operator set), kept apart from the program so that no later
change to the program can move the yardstick.  It imports nothing of
the program: it parses the configuration's netlist itself and runs the
fabric in plain numpy.

One cycle, for every request at once (requests are independent; the
leading axis of every register is the request):

1. feed: an empty input arc takes the next token of its stream;
2. fire: every node whose inputs are full and whose outputs are empty
   fires, all against one snapshot of the registers (COPY duplicates,
   the primitives compute, BRANCH routes by its control, DMERGE selects
   by its control, NDMERGE takes the first full input);
3. const arcs are full again; output arcs drain into the result
   (last value and count).

A request is finished at the first cycle in which nothing fed, fired
or drained (idle is absorbing); its cycle count includes that cycle.

The token type is a parameter: the benchmark runs int32, the type the
configuration states.  The control runs a narrower type (int16), which
a correct comparison must reject on tokens drawn over the whole int32
range.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

# opcode -> (inputs, outputs); asm aliases of the deciders
ARITY = {
    "copy": (1, 2), "add": (2, 1), "sub": (2, 1), "mul": (2, 1),
    "div": (2, 1), "and": (2, 1), "or": (2, 1), "xor": (2, 1),
    "max": (2, 1), "min": (2, 1), "shl": (2, 1), "shr": (2, 1),
    "not": (1, 1), "ifgt": (2, 1), "ifge": (2, 1), "iflt": (2, 1),
    "ifle": (2, 1), "ifeq": (2, 1), "ifdf": (2, 1), "dmerge": (3, 1),
    "ndmerge": (2, 1), "branch": (2, 2), "sink": (1, 0),
}
ALIASES = {"gtdecider": "ifgt", "gedecider": "ifge", "ltdecider": "iflt",
           "ledecider": "ifle", "eqdecider": "ifeq", "dfdecider": "ifdf"}
_STMT = re.compile(r"^(?:\d+\s*\.)?\s*(\w+)\s+(.*)$")


@dataclasses.dataclass
class Fabric:
    nodes: list            # (op, inputs, outputs)
    consts: dict           # arc -> value (sticky bus)
    inits: dict            # arc -> value (one-shot initial token)

    @property
    def arcs(self) -> list:
        seen = {}
        for _, ins, outs in self.nodes:
            for a in (*ins, *outs):
                seen.setdefault(a, None)
        for a in (*self.consts, *self.inits):
            seen.setdefault(a, None)
        return list(seen)

    def input_arcs(self) -> list:
        produced = {a for _, _, outs in self.nodes for a in outs}
        return [a for a in self.arcs if a not in produced
                and a not in self.consts and a not in self.inits]

    def output_arcs(self) -> list:
        consumed = {a for _, ins, _ in self.nodes for a in ins}
        return [a for a in self.arcs if a not in consumed]


def parse(text: str) -> Fabric:
    """The netlist in the paper's assembler syntax (Listing 1)."""
    f = Fabric([], {}, {})
    body = " ".join(line.split("#", 1)[0].split("//", 1)[0]
                    for line in text.splitlines())
    for stmt in body.split(";"):
        stmt = stmt.strip()
        if not stmt:
            continue
        m = _STMT.match(stmt)
        if not m:
            raise SyntaxError(f"bad statement {stmt!r}")
        op, rest = m.group(1).lower(), m.group(2)
        if op in ("const", "init"):
            arc, _, v = rest.partition("=")
            (f.consts if op == "const" else f.inits)[arc.strip()] = \
                int(v.strip(), 0)
            continue
        op = ALIASES.get(op, op)
        if op not in ARITY:
            raise SyntaxError(f"unknown opcode {op!r} in {stmt!r}")
        args = [a.strip() for a in rest.split(",") if a.strip()]
        n_in, n_out = ARITY[op]
        if len(args) != n_in + n_out:
            raise SyntaxError(f"{op} wants {n_in}+{n_out} args: {stmt!r}")
        f.nodes.append((op, tuple(args[:n_in]), tuple(args[n_in:])))
    return f


def alu(op, a, b, dtype):
    """Integer ALU; overflow wraps two's-complement."""
    if op in ("copy", "branch", "sink"):
        return a
    if op == "add": return a + b
    if op == "sub": return a - b
    if op == "mul": return a * b
    if op == "div":
        return np.where(b == 0, 0, a // np.where(b == 0, 1, b))
    if op == "and": return a & b
    if op == "or": return a | b
    if op == "xor": return a ^ b
    if op == "max": return np.maximum(a, b)
    if op == "min": return np.minimum(a, b)
    if op == "shl": return a << np.clip(b, 0, 31).astype(dtype)
    if op == "shr": return a >> np.clip(b, 0, 31).astype(dtype)
    if op == "not": return (a == 0).astype(dtype)
    cmp = {"ifgt": np.greater, "ifge": np.greater_equal, "iflt": np.less,
           "ifle": np.less_equal, "ifeq": np.equal, "ifdf": np.not_equal}
    return cmp[op](a, b).astype(dtype)


@dataclasses.dataclass
class Answer:
    """One request's result: per output arc the last value and count."""
    outputs: dict
    counts: dict
    cycles: int
    fired: int


def run(fabric: Fabric, feeds: list, dtype=np.int32,
        max_cycles: int = 100_000) -> list:
    """Answers of the requests ``feeds`` (each an arc -> stream dict)."""
    with np.errstate(all="ignore"):
        return _run(fabric, feeds, np.dtype(dtype), max_cycles)


def _run(fabric, feeds, dtype, max_cycles):
    S = len(feeds)
    arcs = fabric.arcs
    ix = {a: k for k, a in enumerate(arcs)}
    full = np.zeros((len(arcs), S), bool)          # [arc, request]
    val = np.zeros((len(arcs), S), dtype)
    for a, v in (*fabric.consts.items(), *fabric.inits.items()):
        full[ix[a]] = True
        val[ix[a]] = np.asarray(v).astype(dtype)
    consts = [ix[a] for a in fabric.consts]
    ins = fabric.input_arcs()
    fin = np.array([ix[a] for a in ins], np.int64)
    length = np.array([[len(f.get(a, ())) for f in feeds] for a in ins],
                      np.int64).reshape(len(ins), S)
    stream = np.zeros((len(ins), S, max(int(length.max(initial=0)), 1)),
                      dtype)
    for k, a in enumerate(ins):
        for i, f in enumerate(feeds):
            if a in f:
                stream[k, i, :length[k, i]] = np.asarray(f[a]).astype(dtype)
    ptr = np.zeros((len(ins), S), np.int64)
    fout = np.array([ix[a] for a in fabric.output_arcs()], np.int64)
    out_last = np.zeros((len(fout), S), dtype)
    out_count = np.zeros((len(fout), S), np.int64)
    # plain operators, grouped by opcode: one vector step per group
    groups = {}
    control = []
    for op, i, o in fabric.nodes:
        if op in ("ndmerge", "dmerge", "branch"):
            control.append((op, [ix[x] for x in i], [ix[x] for x in o]))
        else:
            g = groups.setdefault((op, len(i), len(o)), ([], []))
            g[0].append([ix[x] for x in i])
            g[1].append([ix[x] for x in o])
    groups = [(op, np.array(i, np.int64).reshape(len(i), n_i),
               np.array(o, np.int64).reshape(len(o), n_o))
              for (op, n_i, n_o), (i, o) in groups.items()]
    cycles = np.full(S, max_cycles, np.int64)
    fired = np.zeros(S, np.int64)
    live = np.ones(S, bool)
    c = 0
    while live.any() and c < max_cycles:
        # 1. feed
        m = ~full[fin] & (ptr < length)
        tok = np.take_along_axis(
            stream, np.minimum(ptr, stream.shape[2] - 1)[:, :, None],
            axis=2)[:, :, 0]
        val[fin] = np.where(m, tok, val[fin])
        full[fin] |= m
        ptr += m
        progress = m.any(axis=0)
        # 2. fire, every node against one snapshot
        sfull, sval = full.copy(), val.copy()
        consume, produce = [], []
        for op, i, o in groups:
            f = sfull[i].all(axis=1) & ~sfull[o].any(axis=1)   # [n, S]
            z = alu(op, sval[i[:, 0]], sval[i[:, -1]], dtype)
            consume += [(i[:, k], f) for k in range(i.shape[1])]
            produce += [(o[:, k], f, z) for k in range(o.shape[1])]
            fired += f.sum(axis=0) * live
            progress |= f.any(axis=0)
        for op, i, o in control:
            if op == "ndmerge":
                f = (sfull[i[0]] | sfull[i[1]]) & ~sfull[o[0]]
                first = sfull[i[0]]
                consume += [([i[0]], (f & first)[None]),
                            ([i[1]], (f & ~first)[None])]
                produce.append(([o[0]], f[None], np.where(
                    first, sval[i[0]], sval[i[1]])[None]))
            elif op == "dmerge":
                pick_a = sval[i[2]] != 0
                src_full = np.where(pick_a, sfull[i[0]], sfull[i[1]])
                f = sfull[i[2]] & src_full & ~sfull[o[0]]
                consume += [([i[0]], (f & pick_a)[None]),
                            ([i[1]], (f & ~pick_a)[None]),
                            ([i[2]], f[None])]
                produce.append(([o[0]], f[None], np.where(
                    pick_a, sval[i[0]], sval[i[1]])[None]))
            else:                                          # branch
                to_t = sval[i[1]] != 0
                dst_full = np.where(to_t, sfull[o[0]], sfull[o[1]])
                f = sfull[i[0]] & sfull[i[1]] & ~dst_full
                consume += [([i[0]], f[None]), ([i[1]], f[None])]
                produce += [([o[0]], (f & to_t)[None], sval[i[0]][None]),
                            ([o[1]], (f & ~to_t)[None], sval[i[0]][None])]
            fired += f * live
            progress |= f
        for a, m in consume:          # an arc has one receiver (consts
            full[a] &= ~m             # excepted, and they refill below)
        for a, m, z in produce:       # and one sender
            full[a] |= m
            val[a] = np.where(m, z, val[a])
        full[consts] = True
        # 3. drain
        m = full[fout]
        out_last = np.where(m, val[fout], out_last)
        out_count += m
        full[fout] = False
        progress |= m.any(axis=0)
        c += 1
        cycles[live & ~progress] = c
        live &= progress
    outs = fabric.output_arcs()
    return [Answer({a: int(out_last[j, k]) for j, a in enumerate(outs)},
                   {a: int(out_count[j, k]) for j, a in enumerate(outs)},
                   int(cycles[k]), int(fired[k])) for k in range(S)]
