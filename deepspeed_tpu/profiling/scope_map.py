"""From an optimized HLO module's text to (scope, phase) per instruction.

A device trace names each operation by its HLO instruction
(``fusion.648``) and carries no ``op_name``, so the model's named scopes
do not reach it.  The compiled program's text does carry them, as
``metadata={op_name="jit(loss_and_grads)/transpose(jvp(layer))/checkpoint/
rematted_computation/mlp/dot_general" ...}`` on every instruction.
``parse`` reads that text once and gives, for each instruction name,

  scope  the innermost of the model's named scopes on the ``op_name``:
         ``embed``, ``attn``, ``ssm`` (a Mamba mixer: projections, conv,
         scan, gate; the Mamba-1 mixer of models/phi4flash.py on
         ops/selective_scan.py and the Mamba-2 mixer of
         models/granite_hybrid.py on ops/ssd_scan.py alike), ``gmu`` (a gated-memory unit), ``mlp``,
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

One level further down, ``part`` names what an instruction does INSIDE
its scope (``PARTS``: the name the program writes with
``jax.named_scope``, the part it means, the scopes it may follow):

  latent, qkv, rotary, layout, core, gate, diff, out
         the parts of ``attn``: the down-projections to the query and
         key/value latents and the latents' norms (latent attention:
         models/glm4_moe_lite.py, where ``qkv`` is the two
         up-projections and ``layout`` holds the broadcast of the one
         rotated key and the joins); the input projections with their
         bias and split; the rotary embedding of q and k; the reshapes and
         transposes to and from the kernels' head-major layout
         (``layout`` also counts in scope ``layer``, where
         ops/transformer.py leaves the context's transpose back); the
         call of the attention itself, kernels and all the wrapper puts
         around them, or the whole XLA path; a per-head gate; the
         differential combination and its norm; the output projection
         with bias, dropout and residual.
  qk_norm, index, select, align
         four more parts of ``attn`` (models/keye_vl2.py): the RMSNorm
         over every head of q and of k; the indexer's projections, norm
         and rotation (and its scores where no kernel fuses them into
         the select); the exact top-k over every query's causal index
         scores; the alignment term with the indexer's gradients.  There
         ``core`` is the attention restricted to the selected keys.
  mix    one more part of ``attn`` (models/zaya.py, ops/cca.py): what
         compressed convolutional attention does between its projections
         and the unit norm of its heads: the q-k mean, the two causal
         convs over the sequence, the value shift.  There ``qk_norm`` is
         the unit norm of every head and the keys' learned temperature.
  in, conv, scan, gate, out
         the parts of ``ssm`` that a Mamba-2 mixer names
         (models/granite_hybrid.py; the Mamba-1 mixer of
         models/phi4flash.py names none and reads as one lump): the input
         projections and their split; the depthwise causal conv, its
         bias and silu; the softplus of the steps, the chunked scan
         (ops/ssd_scan.py: its kernels and what its wrapper puts around
         them); the gated RMSNorm; the output projection.  ``gate`` and
         ``out`` are also parts of ``attn`` under other names on the
         path (``attn_gate``, ``attn_out``): a name counts only under
         its own scope.
  cast   outside every scope: the engine's casts of the weights to the
         compute dtype (runtime/engine.py ``_cast_weights``): in the
         default apply program, which writes the copy that the grad
         program reads, once an optimizer step; in the grad programs of
         the paths that keep no copy, once a micro-batch, with their
         transposes.
  stack  outside every scope and written by no ``named_scope``: a
         ``lax.scan`` slices its own stacked operands, so an instruction
         whose path ENDS directly in a ``while`` body with one of the
         four primitives a scan writes there (``STACK_OPS``:
         ``dynamic_slice`` and ``squeeze`` to read a layer's entry,
         ``broadcast_in_dim`` and ``dynamic_update_slice`` to write
         one): the layer scan's reads of stacked weights and saved
         carries, its writes of kept residuals and gradient stacks.  The
         v5e's compiler roots most of the reading fusions at the
         ``squeeze``, so the two slice names alone miss them.

A part counts only where it follows one of its scopes on the path with
no other scope between them, so that a ``gate`` elsewhere is none.
``tag`` knows nothing of parts: (scope, phase) are what they were.

Across the scopes, ``region`` names a whole module of the model whose
work lies in several of them (``REGIONS``: ``mtp``, a multi-token-
prediction module: its norms and projection, its block with the block's
own ``attn`` / ``router`` / ``experts``, its pass over the head;
``exit``, the exit work of a stack run several times on the same weights
(models/ouro.py): the final norm after every pass, the exit gate, the
passes over the head, the exit distribution and its KL term): the
outermost region name anywhere on the path, or None.  ``tag`` and
``part`` know nothing of regions.

``live()``, ``live_parts()`` and ``live_regions()`` are the doors for a reader outside the
program (the benchmark's per-layer readers): the maps of the step
programs of the engines alive in this process.  Engines are held by weak
reference, nothing is lowered until one of them is called, and a
program's text is lowered once for both.  The text is the persistent
compile cache's where one is on, and its key leaves metadata out: a
program compiled earlier from a tree with other names, and no other
difference, is served with THAT tree's ``op_name``s.  A program with a
Mosaic call carries source locations in the call's bytes and misses
(seen on the chip: PERF.md section 7, PR 38).
"""

import re
import weakref

SCOPES = ("embed", "attn", "ssm", "gmu", "mlp", "router", "dispatch",
          "experts", "shared", "head", "layer", "hc")
OTHER = "other"
PHASES = ("forward", "recompute", "backward")
CAST_SCOPE = "weight_cast"
# the name on the path -> (part, the scopes it counts in)
PARTS = {
    "attn_latent": ("latent", ("attn",)),
    "attn_qkv": ("qkv", ("attn",)),
    "attn_rotary": ("rotary", ("attn",)),
    "attn_layout": ("layout", ("attn", "layer")),
    "attn_core": ("core", ("attn",)),
    "attn_gate": ("gate", ("attn",)),
    "attn_diff": ("diff", ("attn",)),
    "attn_out": ("out", ("attn",)),
    "attn_qk_norm": ("qk_norm", ("attn",)),
    "attn_index": ("index", ("attn",)),
    "attn_select": ("select", ("attn",)),
    "attn_align": ("align", ("attn",)),
    "attn_mix": ("mix", ("attn",)),
    "ssm_in": ("in", ("ssm",)),
    "ssm_conv": ("conv", ("ssm",)),
    "ssm_scan": ("scan", ("ssm",)),
    "ssm_gate": ("gate", ("ssm",)),
    "ssm_out": ("out", ("ssm",)),
    CAST_SCOPE: ("cast", (OTHER,)),
}
REGIONS = ("mtp", "exit")
STACK = "stack"
STACK_OPS = ("dynamic_slice", "squeeze", "broadcast_in_dim",
             "dynamic_update_slice")

# ``%name = shape opcode(...)`` or ``ROOT %name = ...``; the name is
# what a device trace shows.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# path separators and the wrappers' parentheses both split components
_COMPONENT = re.compile(r"[/()]")
_REMAT = "rematted_computation"
_TRANSPOSE = "transpose("

_engines = []  # weak references, in order of construction
# engine -> the optimized HLO texts of its step programs, in their
# order: one lowering serves live() and live_parts()
_texts = weakref.WeakKeyDictionary()


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


def part(op_name):
    """The part of its scope one ``op_name`` path names, or None."""
    scope, found = OTHER, None
    components = [c for c in _COMPONENT.split(op_name) if c]
    for component in components:
        if component in SCOPES:
            scope, found = component, None
        elif component in PARTS and scope in PARTS[component][1]:
            found = PARTS[component][0]
    if found is None and scope == OTHER and \
            components[-3:-1] == ["while", "body"] and \
            components[-1] in STACK_OPS:
        return STACK
    return found


def region(op_name):
    """The region one ``op_name`` path lies in, or None."""
    return next((c for c in _COMPONENT.split(op_name) if c in REGIONS),
                None)


def _parse(hlo_text, of):
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        named = _OP_NAME.search(line)
        # no metadata reads as an empty path
        out[m.group(1)] = of(named.group(1) if named else "")
    return out


def parse(hlo_text):
    """{instruction name: (scope, phase)} for every instruction of an
    optimized HLO module's text, the bodies of its fusions, loops and
    calls included.  Instruction names are unique within a module."""
    return _parse(hlo_text, tag)


def parse_parts(hlo_text):
    """{instruction name: part or None}, over the same instructions as
    ``parse``."""
    return _parse(hlo_text, part)


def parse_regions(hlo_text):
    """{instruction name: region or None}, over the same instructions as
    ``parse``."""
    return _parse(hlo_text, region)


def register(engine):
    """Called by an engine as it is built; keeps no engine alive."""
    _engines[:] = [r for r in _engines if r() is not None]
    _engines.append(weakref.ref(engine))


def _live(parser):
    maps = {}
    for ref in _engines:
        engine = ref()
        if engine is not None:
            # the engine's list only grows, a program keeps its place
            texts = _texts.setdefault(engine, [])
            for i, (name, text) in enumerate(engine.step_programs()):
                if i == len(texts):
                    texts.append(text())
                maps[name] = parser(texts[i])
    return maps


def live():
    """{program name: {instruction: (scope, phase)}} over the step
    programs the engines alive in this process have launched, a later
    engine's program taking the place of an earlier one's of the same
    name.  Lowers (through the compile cache) each program once, at the
    first call of this or of ``live_parts``: for after a measured
    window, never inside one."""
    return _live(parse)


def live_parts():
    """{program name: {instruction: part or None}} over the same
    programs, from the same texts."""
    return _live(parse_parts)


def live_regions():
    """{program name: {instruction: region or None}} over the same
    programs, from the same texts."""
    return _live(parse_regions)
