"""Profiling utilities.

Rebuild of the reference's --profiling path (reference: FFConfig.profiling
→ Op.profiling → per-kernel cudaEvent timing printed per task,
kernels/linear_kernels.cu:95-117; SURVEY §5.1). On this chip the truth
is the fused step, so the table comes from a trace of the real program:

  * `profile_step(model, batch)` — the `--profiling` table: run the
    compiled train step under the profiler and charge every device
    instruction to the PCG node it was lowered from (the `kind:name`
    scope `Executor.lower_node` opens), forward and backward apart,
    with `loss`, `update` and the collectives. `profile_program` is the
    same for any compiled program (the engine's step programs, whose
    texts `step_program_texts` records), `fold_step` the reduction
    alone, a pure function of (device events, compiled HLO text).
  * `profile_operators(model, batch)` — each PCG node's lowered forward
    jitted ALONE and timed: what the calibration and `search/audit.py`
    need (a cost per node before a program exists), not what a step
    costs. It cannot see fusion across nodes, the backward pass, the
    update, collectives or the mesh lowering.
  * `trace(dir)` — context manager around jax.profiler for a real XLA
    trace (the analog of `-lg:prof` external profiles, viewable in
    TensorBoard / Perfetto), with the program's own host phases in it.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple


def profile_operators(
    model, batch: Dict, iters: int = 5, verbose: bool = True
) -> List[Tuple[str, float]]:
    """Per-op isolated forward times in seconds, slowest first: every
    node jitted alone, so the times over-count what XLA fuses across
    nodes in the real step and hold no backward, update or collective
    (`profile_step` reads those from the step itself)."""
    import jax

    ex = model.executor
    if ex is None:
        raise RuntimeError("call compile() before profile_operators()")
    sharded = ex.shard_batch(batch)
    values = {}
    rows: List[Tuple[str, float]] = []
    from flexflow_tpu.core.types import OperatorType
    from flexflow_tpu.ops.registry import LowerCtx

    for guid in ex.topo:
        node = ex.graph.nodes[guid]
        if node.op_type in (OperatorType.INPUT, OperatorType.NOOP) and not node.inputs:
            if node.name not in sharded:
                raise KeyError(f"batch missing input '{node.name}'")
            values[(guid, 0)] = sharded[node.name]
            continue
        ins = [values[(r.guid, r.out_idx)] for r in node.inputs]
        # per-weight accessor: pipelined trunks store weights stacked
        # under their template guid (Executor.get_host_param slices out
        # this block's weights; plain executors read params[guid] direct)
        ws = [
            ex.get_host_param(model.params, guid, i)
            for i in range(len(node.weight_shapes))
        ]
        # mirror Executor.forward_values' ctx so profiled shapes match the
        # real step (seq_length truncation included)
        ctx = LowerCtx(
            train=False,
            rng=None,
            mesh=ex.mesh,
            axis_names=ex.mesh_config.axis_names,
            in_shapes=[ex.graph.shape_of(r) for r in node.inputs],
            bf16_matmul=ex.mixed_precision,
            seq_length=ex.seq_length,
        )
        fn = ex._lowered[guid]
        jitted = jax.jit(lambda i, w, _fn=fn, _ctx=ctx: _fn(i, w, _ctx))
        outs = jitted(ins, ws)
        jax.block_until_ready(outs)
        t0 = time.perf_counter()
        for _ in range(iters):
            outs = jitted(ins, ws)
        jax.block_until_ready(outs)
        dt = (time.perf_counter() - t0) / iters
        rows.append((node.name, dt))
        for i, out in enumerate(outs):
            values[(guid, i)] = out
    rows.sort(key=lambda r: -r[1])
    if verbose:
        total = sum(t for _, t in rows) or 1e-12
        print(f"{'op':<32} {'time':>12} {'share':>7}")
        for name, t in rows:
            print(f"{name:<32} {t * 1e6:>10.1f}us {t / total:>6.1%}")
    return rows


def xla_cost_analysis(model, batch: Dict) -> Dict[str, float]:
    """XLA's own cost analysis of the compiled train step — flops,
    bytes accessed, and transcendentals as the COMPILER counts them
    after fusion/DCE (the ground truth the analytic cost model
    approximates; the reference has no equivalent, its simulator only
    times kernels). Returns the cost dict of `Compiled.cost_analysis()`.

        model.compile(...); xla_cost_analysis(model, batch)
        # {'flops': 2.1e9, 'bytes accessed': 8.4e8, ...}
    """
    import jax

    ex = model.executor
    if ex is None:
        raise RuntimeError("call compile() before xla_cost_analysis()")
    sharded = ex.shard_batch(batch)
    key = jax.random.PRNGKey(0)
    # reuse the executor's cached jit wrapper (same donation flags, same
    # compiled program the training loop runs; no second full compile)
    lowered = ex.train_step().lower(
        model.params, model.opt_state, sharded, key
    )
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax: one dict per device
        cost = cost[0]
    return dict(cost or {})


@contextlib.contextmanager
def trace(log_dir: str):
    """XLA profiler trace (view in TensorBoard/Perfetto):

        with profiling.trace("/tmp/trace"):
            model.fit(...)

    The session is the one the benchmark's traced runs use: the Python
    tracer off (it would put an event on every call of the host loop)
    and the host tracer at level 1, which keeps `TraceAnnotation`s. So
    the program's own phases (`telemetry.trace.span`: `train.input.*`,
    `train.epoch_end.*`, `scheduler.step.*`) sit on the Python thread's
    line above the device's ops, on one clock
    (docs/observability.md, "Reading a profile")."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# -- the --profiling table, from the real step --------------------------------
#
# A TPU trace holds, per chip, a line `XLA Modules` (one event per executed
# program, `jit_step(<hash>)`) and a line `XLA Ops` (one event per executed
# HLO instruction, named by its text `%fusion.3 = f32[...] fusion(...)`; a
# `while`'s body nests inside the `while`). The compiled executable's own
# text (`compiled.as_text()`) gives every instruction, and every instruction
# inside a fused computation, its `metadata={op_name="jit(step)/..."}`: the
# path of named scopes it was traced under.

Event = Tuple[str, float, float]  # name, start, end (any one unit)

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)
UNSCOPED = "(unscoped)"
#: what a fusion is charged to when its body holds one, else its root
_HEAVY = ("convolution", "dot", "custom-call")
_NODE_SCOPE = re.compile(r"[a-z0-9_]+:[^/()]+")
_WRAPPED = re.compile(r"(?:[\w.\-]+\()*([^()]*)\)*")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z0-9\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations"
    r"|called_computations)=(?:\{([^}]*)\}|(%?[\w.\-]+))"
)
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_COLUMNS = (
    "forward_ms", "backward_ms", "other_ms", "collective_ms", "charged_ms",
    "mixed_ms",
)
_MS_PER_NS = 1e-6  # `read_device_events` gives nanoseconds


class NoDeviceOps(RuntimeError):
    """The trace holds no `XLA Ops` line to fold (the CPU backend's)."""


@dataclasses.dataclass
class DeviceEvents:
    """One chip's `XLA Modules` and `XLA Ops` events."""

    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class _Instruction:
    name: str
    opcode: str
    computation: str
    operands: Tuple[str, ...]
    calls: Tuple[str, ...]
    op_name: str
    root: bool


@dataclasses.dataclass
class ScopeRow:
    """Device milliseconds of one scope per execution of the program,
    averaged over chips. `forward + backward + other` is the row's time;
    `collective`, `charged` (compiler-made instructions charged here
    through their first scoped user) and `mixed` (fusions whose body
    spans more than one scope) are parts of it, not additions."""

    scope: str
    kind: str
    family: str
    forward_ms: float = 0.0
    backward_ms: float = 0.0
    other_ms: float = 0.0
    collective_ms: float = 0.0
    charged_ms: float = 0.0
    mixed_ms: float = 0.0

    @property
    def total_ms(self) -> float:
        return self.forward_ms + self.backward_ms + self.other_ms


@dataclasses.dataclass
class StepProfile:
    program: str
    chips: int
    executions: int  # of the program, one chip's
    device_ms: float  # the program's device time per execution
    rows: List[ScopeRow]  # heaviest first
    #: the twenty heaviest instructions: (name, scope, phase, ms)
    heaviest: List[Tuple[str, str, str, float]] = dataclasses.field(
        default_factory=list
    )

    @property
    def accounted(self) -> float:
        """The rows' sum over the program's device time: what is left
        of 1 is gaps between instructions inside the program."""
        return sum(r.total_ms for r in self.rows) / (self.device_ms or 1e-12)

    def by_family(self) -> List[ScopeRow]:
        out: Dict[str, ScopeRow] = {}
        for r in self.rows:
            f = out.setdefault(r.family, ScopeRow(r.family, r.family, r.family))
            for name in _COLUMNS:
                setattr(f, name, getattr(f, name) + getattr(r, name))
        return sorted(out.values(), key=lambda r: -r.total_ms)

    def table(self, by_family: bool = False) -> str:
        rows = self.by_family() if by_family else self.rows
        head = " ".join(f"{c[:-3]:>10}" for c in _COLUMNS)
        lines = [f"{'family' if by_family else 'scope':<44} {head} {'share':>7}"]
        for r in rows:
            cells = " ".join(f"{getattr(r, c):>10.3f}" for c in _COLUMNS)
            share = r.total_ms / (self.device_ms or 1e-12)
            lines.append(f"{r.scope:<44} {cells} {share:>6.1%}")
        lines.append(
            f"{self.program}: {self.device_ms:.3f} ms of device time a step "
            f"({self.executions} executions a chip, {self.chips} chips), "
            f"rows hold {self.accounted:.1%}"
        )
        return "\n".join(lines)


def scope_of(op_name: str) -> Optional[Tuple[str, str]]:
    """(scope, phase) of an instruction's `op_name`, or None.

    The scope is the first path component that is a node's (`kind:name`),
    `loss` or `update`; failing those the innermost `step.*`. Autodiff
    writes the phase: under `transpose(jvp(X))` the instruction is X's
    backward, under `jvp(X)` or plain `X` its forward; `update` and
    `step.*` are neither (`other`)."""
    backward, step = False, None
    for part in op_name.split(";")[0].split("/"):
        backward = backward or part.startswith("transpose(")
        inner = _WRAPPED.fullmatch(part)
        inner = inner.group(1) if inner else part
        if inner == "update":
            return inner, "other"
        if inner == "loss" or _NODE_SCOPE.fullmatch(inner):
            return inner, "backward" if backward else "forward"
        if inner.startswith("step."):
            step = (inner, "other")
    return step


def _family(kind: str) -> str:
    """`search.cost_model.op_family` of an operator type's name in lower
    case (the reader's mapping: the runtime imports no search for a
    string); a kind without a family, `loss` or `step.pick`, is its own."""
    from flexflow_tpu.core.types import OperatorType
    from flexflow_tpu.search.cost_model import op_family

    try:
        return op_family(OperatorType[kind.upper()]) or kind
    except KeyError:
        return kind


def parse_hlo(text: str) -> Tuple[str, Dict[str, _Instruction]]:
    """(module name, instruction name -> instruction) of a compiled
    executable's text. Instruction names are unique in a module."""
    module = re.search(r"^HloModule\s+([\w.\-]+)", text, re.M)
    instructions: Dict[str, _Instruction] = {}
    computation = ""
    for line in text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _COMPUTATION.match(line)
            if m:
                computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or not computation:
            continue
        root, name, rest = m.groups()
        body = rest.split(", metadata={", 1)[0]
        opcode = _OPCODE.search(body)
        calls: List[str] = []
        for called in _CALLED.finditer(body):
            names = called.group(1) or called.group(2)
            calls += [c.strip().lstrip("%") for c in names.split(",")]
        operands = tuple(
            o for o in re.findall(r"%([\w.\-]+)", _CALLED.sub("", body))
        )
        op_name = _OP_NAME.search(rest)
        instructions[name] = _Instruction(
            name, opcode.group(1) if opcode else "", computation, operands,
            tuple(calls), op_name.group(1) if op_name else "", bool(root),
        )
    return (module.group(1) if module else ""), instructions


class _Charger:
    """Which scope each instruction of a program is charged to."""

    def __init__(self, instructions: Dict[str, _Instruction]):
        self.instructions = instructions
        self.bodies: Dict[str, List[_Instruction]] = {}
        self.users: Dict[str, List[str]] = {}
        for ins in instructions.values():
            self.bodies.setdefault(ins.computation, []).append(ins)
            for operand in ins.operands:
                self.users.setdefault(operand, []).append(ins.name)
        self._memo: Dict[str, Tuple[str, str, bool, bool]] = {}
        self._own_memo: Dict[str, tuple] = {}

    def _own(self, ins: _Instruction):
        """((scope, phase) or None, mixed) from the instruction itself: a
        fusion's from its heaviest instruction, not blindly its root."""
        if ins.name not in self._own_memo:
            self._own_memo[ins.name] = self._read_own(ins)
        return self._own_memo[ins.name]

    def _read_own(self, ins: _Instruction):
        body = [
            b for c in ins.calls if ins.opcode == "fusion"
            for b in self.bodies.get(c, ())
        ]
        if not body:
            return (scope_of(ins.op_name) if ins.op_name else None), False
        heavy = next((b for b in body if b.opcode in _HEAVY), None)
        rootmost = next((b for b in body if b.root), None)
        found = None
        for candidate in (heavy, ins, rootmost):
            if candidate is not None and candidate.op_name:
                found = scope_of(candidate.op_name)
                if found:
                    break
        scopes = {
            got[0] for b in body if b.op_name
            for got in (scope_of(b.op_name),) if got
        }
        return found, len(scopes) > 1

    def charge(self, name: str) -> Tuple[str, str, bool, bool]:
        """(scope, phase, charged through a neighbour, mixed) of an
        executed instruction. One the compiler made (`copy-start`,
        `slice-done`, a layout `copy`, `ragged-dot-*`) carries no scope
        and goes to its first scoped user, breadth first through the
        def-use edges; one with no scoped user (the copy of a result
        into the program's output) to its first scoped producer."""
        if name in self._memo:
            return self._memo[name]
        ins = self.instructions.get(name)
        out = (UNSCOPED, "other", False, False)
        if ins is not None:
            own, mixed = self._own(ins)
            if own:
                out = (*own, False, mixed)
            else:
                found = self._nearest(name, self.users.get) or self._nearest(
                    name, lambda at: self.instructions[at].operands
                )
                if found:
                    out = (*found, True, False)
        self._memo[name] = out
        return out

    def _nearest(self, name: str, edges):
        """The own scope of the nearest instruction along `edges` that
        has one, breadth first, the text's order inside a level."""
        seen, frontier = {name}, [name]
        while frontier:
            nxt = []
            for at in frontier:
                for other in edges(at) or ():
                    if other in seen or other not in self.instructions:
                        continue
                    seen.add(other)
                    got, _ = self._own(self.instructions[other])
                    if got:
                        return got
                    nxt.append(other)
            frontier = nxt
        return None


def _self_times(ops: Sequence[Event]) -> List[Tuple[str, float]]:
    """(name, self time) of each event: its length less its direct
    children's (a `while` holds its body's instructions)."""
    order = sorted(ops, key=lambda o: (o[1], -(o[2] - o[1])))
    own = [end - start for _, start, end in order]
    stack: List[int] = []
    for i, (_, start, end) in enumerate(order):
        while stack and order[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= order[stack[-1]][2]:
            own[stack[-1]] -= end - start
        stack.append(i)
    return [(o[0], max(t, 0.0)) for o, t in zip(order, own)]


def fold_step(
    devices: Sequence[DeviceEvents],
    hlo_text: str,
    program: Optional[str] = None,
) -> StepProfile:
    """The per-scope table of one program from device events and the
    program's compiled text: a pure function (tests/test_node_scopes.py
    holds it to hand-made events and to a recorded v5e trace).

    Kept are the `XLA Ops` events inside the `XLA Modules` events of the
    text's module; each is charged its SELF time (`_Charger.charge`
    says to which scope and phase), per execution, averaged over chips.
    Where a trace holds several executables of one module name (the
    prefill's buckets), `program` names one with its hash, as the
    `XLA Modules` line does: `jit__prefill_impl_paged(5173830649487626103)`.
    Events are in nanoseconds, rows in milliseconds."""
    module, instructions = parse_hlo(hlo_text)
    program = program or module
    charger = _Charger(instructions)
    rows: Dict[str, ScopeRow] = {}
    by_name: Dict[str, float] = {}
    device_time, executions, chips = 0.0, 0, 0
    for dev in devices:
        named = {
            name for name, _, _ in dev.modules
            if program in (name, name.split("(")[0])
        }
        if len(named) > 1:
            raise ValueError(
                f"several executables named {program!r} in this trace: "
                f"name one of {sorted(named)} as `program`"
            )
        runs = sorted((s, e) for name, s, e in dev.modules if name in named)
        if not runs:
            continue
        chips += 1
        executions += len(runs)
        device_time += sum(e - s for s, e in runs)
        for text, own in _self_times(_inside(dev.ops, runs)):
            name = text.split("=", 1)[0].strip().lstrip("%")
            scope, phase, charged, mixed = charger.charge(name)
            by_name[name] = by_name.get(name, 0.0) + own
            row = rows.get(scope)
            if row is None:
                kind = scope.split(":", 1)[0]
                row = rows[scope] = ScopeRow(scope, kind, _family(kind))
            setattr(row, f"{phase}_ms", getattr(row, f"{phase}_ms") + own)
            if name.startswith(COLLECTIVES):
                row.collective_ms += own
            if charged:
                row.charged_ms += own
            if mixed:
                row.mixed_ms += own
    if not executions:
        raise NoDeviceOps(
            f"no device ops in this trace: no chip's `XLA Modules` line "
            f"holds an execution of {program!r} (the CPU backend writes "
            "no device plane; a TPU's profile does)"
        )
    scale = _MS_PER_NS / executions  # summed over chips: a chip's execution
    for row in rows.values():
        for name in _COLUMNS:
            setattr(row, name, getattr(row, name) * scale)
    heaviest = sorted(by_name.items(), key=lambda kv: -kv[1])[:20]
    return StepProfile(
        program, chips, executions // chips, device_time * scale,
        sorted(rows.values(), key=lambda r: -r.total_ms),
        [(n, *charger.charge(n)[:2], t * scale) for n, t in heaviest],
    )


def _inside(ops: Sequence[Event], runs: Sequence[Tuple[float, float]]):
    """The events that lie inside one of the sorted, disjoint `runs`."""
    starts = [s for s, _ in runs]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[2] <= runs[i][1]:
            out.append(op)
    return out


def newest_xplane(log_dir: str) -> str:
    """The newest `.xplane.pb` under `log_dir`, or `log_dir` if a file."""
    if not os.path.isdir(log_dir):
        return log_dir
    found = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_device_events(log_dir: str) -> List[DeviceEvents]:
    """The chips' events of the newest profile under `log_dir` (or of
    the `.xplane.pb` file it names)."""
    from jax.profiler import ProfileData

    path = newest_xplane(log_dir)
    def events(line):
        return [
            (e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events
        ]

    devices = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" in lines and "XLA Modules" in lines:
            found = DeviceEvents(
                events(lines["XLA Modules"]), events(lines["XLA Ops"])
            )
            devices.append((plane.name, found))
    if not devices:
        raise NoDeviceOps(
            f"no device ops in this trace ({path}): it holds no `XLA Ops` "
            "line (the CPU backend writes none; a TPU's profile does)"
        )
    return [events for _, events in sorted(devices, key=lambda d: d[0])]


@contextlib.contextmanager
def fresh_compile():
    """Inside the block a compile is the compiler's own and not one
    fetched from JAX's persistent cache. The cache's key leaves an
    instruction's metadata out, so a fetched executable carries the
    `op_name`s of whichever tree wrote the entry (a parent's, without
    these scopes: seen on the chip, PR 35); its instructions' names are
    the same either way."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def profile_program(
    hlo_text: str,
    run: Callable[[], object],
    steps: int = 1,
    log_dir: Optional[str] = None,
    verbose: bool = True,
    program: Optional[str] = None,
) -> StepProfile:
    """Call `run()` `steps` times inside one `trace()` session (so the
    program's host spans are in the same file on the same clock), wait
    for what it returns, and fold the device's events of the program
    whose compiled text is `hlo_text` (`program`: as `fold_step`'s).
    The profile stays in `log_dir` if one is given, with the program's
    text beside it (`<module>.hlo.txt`)."""
    import jax

    with contextlib.ExitStack() as stack:
        if log_dir is None:
            log_dir = stack.enter_context(tempfile.TemporaryDirectory())
        else:  # what a later `fold_step` of the kept profile needs
            os.makedirs(log_dir, exist_ok=True)
            name = os.path.join(log_dir, parse_hlo(hlo_text)[0] + ".hlo.txt")
            with open(name, "w") as f:
                f.write(hlo_text)
        with trace(log_dir):
            for _ in range(steps):
                jax.block_until_ready(run())
        profile = fold_step(read_device_events(log_dir), hlo_text, program)
    if verbose:
        print(profile.table(by_family=True))
        print(profile.table())
    return profile


def profile_step(
    model,
    batch: Dict,
    steps: int = 8,
    log_dir: Optional[str] = None,
    verbose: bool = True,
) -> StepProfile:
    """The `--profiling` table of the train step as it runs: `steps`
    executions of the executor's own compiled step program (donation
    and all) on COPIES of the parameters and the optimizer state, which
    the model keeps as they were, after one execution outside the trace."""
    import jax
    import jax.numpy as jnp

    ex = model.executor
    if ex is None:
        raise RuntimeError("call compile() before profile_step()")
    sharded = ex.shard_batch(batch)
    key = jax.random.PRNGKey(0)
    state = jax.tree_util.tree_map(jnp.copy, (model.params, model.opt_state))
    with fresh_compile():
        compiled = ex.train_step().lower(*state, sharded, key).compile()

    def run():
        nonlocal state
        *state, loss, _ = compiled(*state, sharded, key)
        return loss

    jax.block_until_ready(run())
    return profile_program(compiled.as_text(), run, steps, log_dir, verbose)


@contextlib.contextmanager
def step_program_texts(engine):
    """{"<module name> <input shapes>": compiled HLO text} of every step
    program `engine` dispatches inside the block, each compiled once
    more (`fresh_compile`) from the arguments of its first dispatch:
    what `fold_step` needs beside a serving trace
    (`jit__decode_impl_paged`, ...)."""
    import jax

    texts: Dict[str, str] = {}
    seen = set()
    run_step = engine._run_step

    def recording(site, step_fn, params, inputs, adapter_args=(), **kw):
        fn = step_fn()
        shapes = tuple(
            jax.numpy.shape(x) for x in jax.tree_util.tree_leaves(inputs)
        )
        if (fn, shapes) not in seen:
            seen.add((fn, shapes))
            text = fn.lower(
                params, *inputs, *engine.cache.pools, *adapter_args
            )
            with fresh_compile():
                text = text.compile().as_text()
            texts[f"{parse_hlo(text)[0]} {shapes}"] = text
        return run_step(site, step_fn, params, inputs, adapter_args, **kw)

    engine._run_step = recording
    try:
        yield texts
    finally:
        del engine._run_step
