"""The program's device phases, read from the compiled program.

The program names its phases with ``jax.named_scope("repro.<phase>")``
(``repro.obs.scope``).  The scope stack reaches the compiled HLO as each
instruction's ``op_name`` metadata (``jit(apply)/repro.apply/while/body/
repro.spmv/...``); an instruction belongs to the innermost ``repro.*``
component of its path.  A fusion carries the metadata of its root, so
the ops fused into it count under the root's phase.

The compiler adds instructions of its own, with no phase in their
metadata.  A ``copy`` that changes its operand's layout is the form a
transpose takes once the TPU compiler has assigned layouts (the
(B, n) <-> (n, B128) turns around each kernel become a bitcast and such
a copy), so it counts as ``repro.layout``.  Any other such instruction
takes the phase of its nearest user that has one (the root of a loop
body passes to the loop), else of its nearest operand.  A program with
no ``repro.*`` phase at all gives an empty map.

The device trace names an op by its HLO instruction, so
:func:`instruction_scopes` of the program that ran labels every op of
the trace; :func:`of_cell` reads it for a cell's timed entry.
"""
import re

#: ``%name = ... metadata={op_name="..." ...}``, one HLO instruction
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$")
#: ``%name (params) -> result {``, the head of a computation
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s+\(.*->.*\{\s*$")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
PHASE = re.compile(r"^repro\.[A-Za-z_]+$")
KIND = re.compile(r"(?:^|[\s}])([a-z][a-z0-9_-]*)\(")
#: the layout of a result shape, memory space left out
LAYOUT = re.compile(r"^[^{(]*\{([^}]*)\}")
CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
REFERENCE = re.compile(r"%([\w.\-]+)")
LAYOUT_PHASE = "repro.layout"


def innermost(op_name):
    """The innermost ``repro.*`` component of an ``op_name`` path, or
    None."""
    for part in reversed(op_name.split("/")):
        if PHASE.match(part):
            return part
    return None


def instructions(hlo_text):
    """[(instruction name, its HLO line after ``=``, op_name or None)] of
    every computation of a compiled program's HLO text."""
    out = []
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m is None:
            continue
        meta = OP_NAME.search(m.group(2))
        out.append((m.group(1), m.group(2), meta.group(1) if meta else None))
    return out


def _layout(rest):
    m = LAYOUT.match(rest)
    return re.sub(r"S\(\d+\)", "", m.group(1)) if m else None


def instruction_scopes(hlo_text):
    """{instruction name: its ``repro.*`` phase} for every instruction of
    a compiled program that has one, by the rules of this module."""
    rest, phase, operands, users, caller = {}, {}, {}, {}, {}
    computation, roots = None, {}
    for line in (hlo_text or "").splitlines():
        head = COMPUTATION.match(line)
        if head is not None:
            computation = head.group(1)
            continue
        m = INSTRUCTION.match(line)
        if m is None:
            continue
        name, body = m.group(1), m.group(2)
        rest[name] = body
        meta = OP_NAME.search(body)
        if meta and innermost(meta.group(1)):
            phase[name] = innermost(meta.group(1))
        if line.lstrip().startswith("ROOT"):
            roots[name] = computation
        for called in CALLED.findall(body):
            caller[called] = name
    if not phase:
        return {}
    for name, body in rest.items():
        operands[name] = [r for r in REFERENCE.findall(body.split("),")[0])
                          if r in rest and r != name]
        for r in operands[name]:
            users.setdefault(r, []).append(name)
    for name, comp in roots.items():
        if comp in caller:
            users.setdefault(name, []).append(caller[comp])

    def relayout(name):
        m = KIND.search(rest[name])
        ops = operands[name]
        return (m is not None and m.group(1) == "copy" and len(ops) == 1
                and _layout(rest[name]) != _layout(rest[ops[0]]))

    def nearest(name, step):
        seen, front = {name}, [name]
        while front:
            nxt = []
            for n in front:
                for r in step.get(n, ()):
                    if r in seen:
                        continue
                    seen.add(r)
                    if r in phase:
                        return phase[r]
                    if relayout(r):
                        return LAYOUT_PHASE
                    nxt.append(r)
            front = nxt
        return None

    out = dict(phase)
    for name in rest:
        if name in phase:
            continue
        found = (LAYOUT_PHASE if relayout(name)
                 else nearest(name, users) or nearest(name, operands))
        if found is not None:
            out[name] = found
    return out


def of_cell(cell):
    """The phase map of the program a cell times (``plan.compiled("apply")``
    at the cell's batch), kept on the cell once read.  The program is
    already compiled by then, so this is a cache hit."""
    phases = getattr(cell, "phases", None)
    if phases is None:
        import jax
        import numpy as np

        x = jax.ShapeDtypeStruct((cell.batch, cell.graph.n), np.float32,
                                 sharding=cell.sharding)
        compiled = cell.plan.compiled("apply").lower(x).compile()
        phases = cell.phases = instruction_scopes(compiled.as_text())
    return phases


def phase_share(events, phases, phase):
    """Busy seconds of the traced window in ops of `phase` (None: in ops
    outside every phase) over all busy seconds, averaged over the
    devices of `events` (a :class:`bench.devtrace.Events`)."""
    mine = sum(events.busy(d, pick=lambda n: phases.get(n) == phase)
               for d in events.ops) / len(events.ops)
    return mine / events.busy_s()
