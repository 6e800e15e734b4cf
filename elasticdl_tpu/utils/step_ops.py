"""The device half of a step under the program's names.

The TPU's trace names an op by its HLO line (``%fusion.1916 = ...``)
and carries no scope, so nothing in it says whether an op is the
forward pass, the backward pass, a recomputed forward or the optimizer.
The compiled module's text does: every instruction carries
``metadata={op_name="..."}`` with JAX's whole name stack, and the
transformations write the pass into it (``jvp(``, ``transpose(``,
``rematted_computation``). Two scopes of ours name what they do not:
``edl/optimizer`` and ``edl/reduce`` in
``parallel.elastic.make_elastic_train_step``.

This module joins the two, text in and dicts out (no jax import, so it
is tested on the CPU at no cost):

- :func:`classify`: a name stack -> the classes it holds;
- :func:`op_classes`: a compiled module's text -> each instruction
  that runs as an op of its own (entry, loop bodies and conditions,
  branches, called computations) with the classes it holds; a fusion
  holds those of every instruction fused into it;
- :func:`ops_under`: the same walk for one ``jax.named_scope`` of a
  model's (``edl/mtp``): the instructions that lie under it whole
  (``in``) and those the compiler fused with work from outside it
  (``in+out``);
- :func:`split_by_class`: the trace's ops of the train-step module +
  that map -> self time by class and by op.

A class is one of :data:`CLASSES`; an op that holds more than one is
written joined, ``bwd+optimizer``, and is MIXED. A mixed op's time is
never divided between its classes: which pairs the compiler fuses is
itself the finding. docs/observability.md "The device step's classes".
"""

import re

OPTIMIZER_SCOPE = "edl/optimizer"
REDUCE_SCOPE = "edl/reduce"
CLASSES = ("fwd", "bwd", "remat", "optimizer", "reduce")
MIXED = "mixed"
UNNAMED = "unnamed"
# the map a traced process writes beside its trace
FILE_NAME = "edl_step_ops.json"

# what ``step_built`` gains in a traced run (ElasticDPTrainer.describe_step)
STEP_BUILT_FIELDS = (
    "step_ops_named", "step_ops_total",
    "step_argument_bytes", "step_temp_bytes", "step_alias_bytes",
)  # fmt: skip

# opcodes that never run: the trace holds no event of theirs, so they
# are counted neither as named nor in the total
_NO_OP = frozenset(
    ("parameter", "get-tuple-element", "tuple", "bitcast", "constant")
)
# the computations an instruction names that run as ops of their own
# (the trace nests them inside the instruction's event); every other
# computation it names (a fusion's, a reduction's) is part of it
_RUNS_ITS_OPS = {
    "while": ("body", "condition"),
    "conditional": (
        "branch_computations", "true_computation", "false_computation",
    ),
    "call": ("to_apply",),
}  # fmt: skip
_COMPUTATION_ATTR = re.compile(
    r"\b(calls|to_apply|body|condition|branch_computations|"
    r"true_computation|false_computation|called_computations)="
    r"(\{[^}]*\}|%?[\w.\-]+)"
)
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def classify(op_name):
    """The classes a ``metadata`` ``op_name`` holds, a frozenset of
    :data:`CLASSES`. One name stack gives at most one: ``optimizer`` or
    ``reduce`` where a scope of ours is in it, else ``remat`` (JAX
    recomputes a checkpointed forward under ``rematted_computation``,
    which sits inside ``transpose(``), else ``bwd`` (``transpose(``),
    else ``fwd`` (``jvp(``; an op on a value whose gradient is stopped
    is still under it). Several stacks joined by ``;`` (ops the
    compiler merged) give each its class."""
    found = set()
    for stack in op_name.split(";"):
        if OPTIMIZER_SCOPE in stack:
            found.add("optimizer")
        elif REDUCE_SCOPE in stack:
            found.add("reduce")
        elif "rematted_computation" in stack:
            found.add("remat")
        elif "transpose(" in stack:
            found.add("bwd")
        elif "jvp(" in stack:
            found.add("fwd")
    return frozenset(found)


def joined(classes):
    """``{"optimizer", "bwd"}`` -> ``"bwd+optimizer"``; nothing -> ``""``."""
    return "+".join(sorted(classes))


def module_name(hlo_text):
    """``HloModule jit_per_device, ...`` -> ``jit_per_device``: what the
    trace's ``XLA Modules`` line calls an execution of it."""
    found = re.search(r"^HloModule\s+([^\s,]+)", hlo_text, re.MULTILINE)
    return found.group(1) if found else ""


def _opcode(rest):
    """The opcode of ``<type> <opcode>(operands), attributes``. A tuple
    type holds spaces and parentheses, an array type neither."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1 :]
                break
    else:
        rest = rest.partition(" ")[2]
    return rest.lstrip().partition("(")[0]


def _computations(hlo_text):
    """(entry, {computation: [(instruction, opcode, op_name, {attribute:
    [computation]})]}) of a module's text, as ``as_text()`` prints it:
    a computation opens with an unindented ``[ENTRY] %name (...) -> ...
    {`` and closes with ``}``."""
    entry, computations, current = None, {}, None
    for line in hlo_text.splitlines():
        if current is None:
            if line.endswith("{") and " -> " in line and line[:1] not in " \t":
                head = line.split()
                is_entry = head[0] == "ENTRY"
                name = head[1 if is_entry else 0].lstrip("%")
                current = computations.setdefault(name, [])
                if is_entry:
                    entry = name
            continue
        if line.startswith("}"):
            current = None
            continue
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name, rest = found.groups()
        body, _, metadata = rest.partition(", metadata={")
        op_name = _OP_NAME.search(metadata)
        named = {}
        for attribute, value in _COMPUTATION_ATTR.findall(body):
            named.setdefault(attribute, []).extend(
                c.strip().lstrip("%") for c in value.strip("{}").split(",")
            )
        current.append(
            (name, _opcode(body), op_name.group(1) if op_name else "", named)
        )
    return entry, computations


def inside(scope):
    """A rule like :func:`classify` for ONE named scope: a name stack
    is ``in`` where ``scope`` is a whole part of its path and ``out``
    where it is not; an instruction without a name says nothing."""
    part = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")

    def membership(op_name):
        return frozenset(
            "in" if part.search(stack) else "out"
            for stack in op_name.split(";")
            if stack
        )

    return membership


def model_scopes(hlo_text):
    """The named scopes of ours a module's text holds but for the two
    that are classes (``edl/mtp``, ``edl/mla``, ...), sorted."""
    found = set(re.findall(r"[/(](edl/[a-z_0-9]+)[/)\"]", hlo_text))
    return sorted(found - {OPTIMIZER_SCOPE, REDUCE_SCOPE})


def ops_under(hlo_text, scope, parsed=None):
    """``{instruction: "in" | "in+out"}`` of the instructions that run
    as ops of their own and hold work under ``scope``: ``in`` where
    every name stack of theirs (a fusion's: of everything fused into
    it) lies under it, ``in+out`` where the compiler fused work from
    outside it in. A reader sums the first and never divides the
    second."""
    return {
        name: held
        for name, held in op_classes(hlo_text, inside(scope), parsed).items()
        if "in" in held.split("+")
    }


def op_classes(hlo_text, classify=classify, parsed=None):
    """``{instruction: classes}`` of an optimized module's text, the
    classes joined (:func:`joined`; ``""`` where the rule classes
    nothing), for every instruction that runs as an op of its own: those
    of the entry, of ``while`` bodies and conditions, of branches and of
    called computations, but for the opcodes that never run. An
    instruction holds the classes of its own ``op_name`` and of every
    instruction of the computations it names that do not run as ops of
    their own (a fusion's ``calls=``, a reduction's ``to_apply=``,
    followed to any depth), so no fused computation's inner instruction
    is a key. Instruction names are unique in a module, so one flat
    dict serves loops too. ``parsed``, where given, is
    ``_computations(hlo_text)``, read once for several rules."""
    entry, computations = parsed or _computations(hlo_text)
    part_classes = {}  # computation -> classes of everything inside it

    def classes_inside(computation):
        if computation not in part_classes:
            part_classes[computation] = frozenset()  # a cycle adds nothing
            found = set()
            for _, _, op_name, named in computations.get(computation, ()):
                found |= classify(op_name)
                for names in named.values():
                    for inner in names:
                        found |= classes_inside(inner)
            part_classes[computation] = frozenset(found)
        return part_classes[computation]

    out, seen, todo = {}, set(), [entry] if entry else []
    while todo:
        computation = todo.pop()
        if computation in seen:
            continue
        seen.add(computation)
        for name, opcode, op_name, named in computations.get(computation, ()):
            runs = _RUNS_ITS_OPS.get(opcode, ())
            found = set(classify(op_name))
            for attribute, names in named.items():
                if attribute in runs:
                    todo.extend(names)
                else:
                    for inner in names:
                        found |= classes_inside(inner)
            if opcode not in _NO_OP:
                out[name] = joined(found)
    return out


def step_ops_map(hlo_text):
    """What a traced process writes beside its trace (:data:`FILE_NAME`),
    ``{"module": name, "ops": {instruction: classes}, "scopes": {scope:
    {instruction: "in" | "in+out"}}}`` with only the instructions the
    rule classes, the model's named scopes the text holds
    (:func:`model_scopes`, :func:`ops_under`), and how many
    instructions run in all (``step_built`` says both counts)."""
    parsed = _computations(hlo_text)
    classes = op_classes(hlo_text, parsed=parsed)
    ops = {name: c for name, c in classes.items() if c}
    scopes = {
        scope: ops_under(hlo_text, scope, parsed)
        for scope in model_scopes(hlo_text)
    }
    return (
        {"module": module_name(hlo_text), "ops": ops, "scopes": scopes},
        len(classes),
    )


def instruction_name(event_name):
    """``%fusion.1916 = s32[1,16]{...} fusion(...)`` -> ``fusion.1916``:
    the TPU's trace puts the whole HLO line in an op event's name."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def self_times(events):
    """Each ``(name, start, end)``'s length minus the events nested
    directly in it, in the events' order. Events of one trace line nest
    properly (an op inside a ``while``) or do not overlap."""
    order = sorted(
        range(len(events)),
        key=lambda i: (events[i][1], -(events[i][2] - events[i][1])),
    )
    own = [end - start for _, start, end in events]
    stack = []
    for i in order:
        while stack and events[stack[-1]][2] <= events[i][1]:
            stack.pop()
        if stack:
            own[stack[-1]] -= events[i][2] - events[i][1]
        stack.append(i)
    return own


def stable_name(event_name):
    """``%fusion.123 = bf16[8,2047]{1,0} fusion(...)`` ->
    ``fusion_bf16_8_2047_``: the kind of name the benchmark's breakdown
    prints, which stays when the compiler renumbers."""
    name, _, rest = event_name.partition(" = ")
    base = re.sub(r"[.\d]+$", "", name.strip().lstrip("%"))
    found = re.match(r"\s*\(?([a-z]+\d*)\[([\d,]*)\]", rest)
    if not found:
        return base
    dims = [d for d in found.group(2).split(",") if d]
    return "_".join([base, found.group(1)] + dims) + "_"


def bucket_of(classes):
    """Where an op's joined classes are summed: under the class itself,
    under ``mixed`` for more than one, under ``unnamed`` for none."""
    if not classes:
        return UNNAMED
    return MIXED if "+" in classes else classes


def split_by_class(events, ops, into=None):
    """``events``: ``(event name, start, end)`` of the ops that ran
    inside executions of the train-step module on one device; ``ops``:
    the map's ``ops``. Returns ``{bucket: {(stable name, classes):
    [self time, calls]}}``, the buckets :data:`CLASSES`, ``mixed`` and
    ``unnamed`` (an op the map lacks, or one the rule classes nothing
    of); added to ``into`` where given (a further device's)."""
    split = {} if into is None else into
    for (name, _, _), own in zip(events, self_times(events)):
        classes = ops.get(instruction_name(name), "")
        entry = split.setdefault(bucket_of(classes), {}).setdefault(
            (stable_name(name), classes), [0.0, 0]
        )
        entry[0] += own
        entry[1] += 1
    return split
