"""From an optimized HLO module's text to (scope, phase) per instruction.

A device trace names each operation by its HLO instruction
(``fusion.648``) and carries no ``op_name``, so the model's named scopes
do not reach it.  The compiled program's text does carry them, as
``metadata={op_name="jit(loss_and_grads)/transpose(jvp(layer))/checkpoint/
rematted_computation/mlp/dot_general" ...}`` on every instruction.
``parse`` reads that text once and gives, for each instruction name,

  scope  the innermost of the model's named scopes on the ``op_name``:
         ``embed``, ``attn``, ``ssm`` (a Mamba mixer: projections, conv,
         selective scan, gate), ``gmu`` (a gated-memory unit), ``mlp``,
         the four of a sparse FFN (moe/dropless.py): ``router`` (scores,
         top-k, counts), ``dispatch`` (the sorts, the gather of rows and
         the weighted gather back), ``experts`` (the grouped products
         and the gate between them), ``shared`` (the shared expert);
         ``head``; ``layer`` for what is in a layer and in no mixer's
         scope nor ``mlp``; ``other`` where no
         scope is named or the instruction has no metadata.  A scope can
         sit inside ``jvp(...)`` or ``transpose(...)``; those are looked
         into.
  phase  ``forward`` (no ``transpose(`` on the path), ``recompute``
         (``rematted_computation`` on the path: the forward pass run
         again inside the backward pass), else ``backward``.

A fusion takes the tag of its own instruction's metadata (XLA gives a
fusion the metadata of its root), not of what it fused.

``live()`` is the one door for a reader outside the program (the
benchmark's per-layer readers): the maps of the step programs of the
engines alive in this process.  Engines are held by weak reference, and
nothing is lowered until it is called.
"""

import re
import weakref

SCOPES = ("embed", "attn", "ssm", "gmu", "mlp", "router", "dispatch",
          "experts", "shared", "head", "layer")
OTHER = "other"
PHASES = ("forward", "recompute", "backward")

# ``%name = shape opcode(...)`` or ``ROOT %name = ...``; the name is
# what a device trace shows.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# path separators and the wrappers' parentheses both split components
_COMPONENT = re.compile(r"[/()]")
_REMAT = "rematted_computation"
_TRANSPOSE = "transpose("

_engines = []  # weak references, in order of construction


def tag(op_name):
    """(scope, phase) of one ``op_name`` path."""
    scope = OTHER
    for part in _COMPONENT.split(op_name):
        if part in SCOPES:
            scope = part  # the innermost is the last one on the path
    if _TRANSPOSE not in op_name:
        phase = "forward"
    elif _REMAT in op_name:
        phase = "recompute"
    else:
        phase = "backward"
    return scope, phase


def parse(hlo_text):
    """{instruction name: (scope, phase)} for every instruction of an
    optimized HLO module's text, the bodies of its fusions, loops and
    calls included.  Instruction names are unique within a module."""
    tags = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        named = _OP_NAME.search(line)
        # no metadata reads as an empty path: ("other", "forward")
        tags[m.group(1)] = tag(named.group(1) if named else "")
    return tags


def register(engine):
    """Called by an engine as it is built; keeps no engine alive."""
    _engines[:] = [r for r in _engines if r() is not None]
    _engines.append(weakref.ref(engine))


def live():
    """{program name: {instruction: (scope, phase)}} over the step
    programs the engines alive in this process have launched, a later
    engine's program taking the place of an earlier one's of the same
    name.  Lowers (through the compile cache) each program once per
    call: for after a measured window, never inside one."""
    maps = {}
    for ref in _engines:
        engine = ref()
        if engine is not None:
            for name, text in engine.step_programs():
                maps[name] = parse(text())
    return maps
