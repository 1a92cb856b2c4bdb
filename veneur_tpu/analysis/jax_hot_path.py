"""vtlint pass: no hidden host syncs or jit-boundary hazards in the
warm per-batch/per-flush functions.

The ingest arc's perf contract: once warm, a batch crosses the host ->
device boundary exactly once (the packed h2d feed) and nothing on the
pipeline thread ever waits on the device. Three regression classes this
pass catches mechanically:

1. **Implicit host syncs on device values** — `float()` / `int()` /
   `bool()` / `np.asarray()` / `np.array()` / `.item()` / `.tolist()`
   applied to a traced or device-derived value blocks the caller until
   every queued device computation lands (the exact bug fixed in
   sharded _apply_hll_imports: `np.array(self.state.hll)` stalled
   swap() — and therefore ingest — behind the full step backlog).
   Host-side numpy values are fine; a cheap taint walk tells them
   apart: device roots are `self.state` / a `state` parameter, any
   `jax.*`/`jax.numpy.*` call result, and locals assigned from either.
2. **Python branching on traced values** — an `if`/`while` whose test
   touches a device value is a host sync in disguise.
3. **Jit-boundary hazards** — `jax.block_until_ready` in production
   code (bench/deliberate drain points carry a reasoned suppression);
   the donating jit wrappers losing their `donate_argnums`/
   `donate_argnames` (the donation contract the double-buffered packed
   feed depends on — without it every step copies DeviceState); and
   call sites passing list/dict/set literals for the static `spec`/
   `sizes` args of the jitted family (unhashable statics throw at
   trace time; a fresh tuple per call recompiles).
4. **Host code inside Pallas kernels** — the body of any function
   handed to `pl.pallas_call` is device code: every parameter is a Ref
   (or a value loaded from one), so a Python `if`/`while` on one, or a
   `float()`/`np.asarray()`/`.item()` host conversion, either fails at
   trace time on TPU or — worse — silently "works" in interpret mode
   and then diverges on hardware. Structured control flow belongs in
   `@pl.when` / `lax.cond` / `lax.fori_loop`. Kernels are resolved
   from the call site (a bare name or `functools.partial(name, ...)`)
   so nested closure kernels are scanned too; keyword-only kernel
   params are treated as host statics (the `functools.partial`
   convention) and stay untainted.
5. **Host code inside shard_map bodies** — a function handed to
   `shard_map` traces per-device-tile exactly like a kernel: every
   positional parameter is a device shard, so the same host-sync and
   traced-branching rules apply. Additionally, every collective in a
   body must name its mesh axis: `lax.psum(x, ...)`-family calls with
   the axis argument MISSING rely on implicit axis context that does
   not exist under shard_map (trace-time error at best), and a bare
   NUMERIC axis silently means a positional array axis on several of
   these APIs — the reduce happens inside one shard instead of across
   the mesh. A string literal or a named constant (REPLICA_AXIS /
   SHARD_AXIS) passes.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set

from veneur_tpu.analysis.core import FileContext, Finding, Project

NAME = "jax-hot-path"
DOC = ("warm per-batch/per-flush functions contain no implicit host "
       "syncs, traced-value branching, or jit-boundary hazards")

# the hot-path-alloc set, extended with the per-flush warm paths that
# run on (and block) the pipeline thread
HOT_FUNCS: Dict[str, List[str]] = {
    "veneur_tpu/server/native_aggregator.py": [
        "_emit_native", "feed", "pump", "_split_shards"],
    "veneur_tpu/aggregation/step.py": ["pack_batch"],
    "veneur_tpu/server/aggregator.py": [
        "_on_batch", "_flush_hll_imports", "swap", "query_snapshot"],
    "veneur_tpu/server/sharded_aggregator.py": [
        "_dispatch_row", "_on_shard_batch", "_emit_all",
        "_apply_hll_imports", "swap", "query_snapshot"],
    "veneur_tpu/collective/tier.py": [
        "_dispatch_routed", "_on_stage_batch", "absorb_raw", "swap",
        "query_snapshot"],
    "veneur_tpu/query/engine.py": [
        "_launch", "_launch_on_pipeline", "_launch_combined"],
    # history ring maintenance runs inside the flush's dispatch window
    # on the pipeline/flush thread: a hidden sync here stalls swap()
    "veneur_tpu/history/writer.py": [
        "begin_flush", "commit_flush", "_roll", "record_frame"],
}

# named jit wrappers that MUST donate their state argument: dropping
# donate_argnums/donate_argnames silently doubles per-step HBM traffic
DONATING_JITS: Dict[str, List[str]] = {
    "veneur_tpu/aggregation/step.py": [
        "ingest_step", "ingest_step_packed", "compact"],
    # the ring mutators update HistoryState in place; losing donation
    # doubles the history tier's HBM footprint per flush
    "veneur_tpu/history/device.py": [
        "write_window", "wipe_rows", "roll_tiers"],
}

# static parameters of the jitted family: a list/dict/set literal here
# is unhashable (TypeError at trace time)
STATIC_ARG_NAMES = ("spec", "sizes", "hspec")
JITTED_CALLEES = ("ingest_step", "packed_step", "compact",
                  "flush_compute", "quantile_compute",
                  "write_window", "wipe_rows", "roll_tiers",
                  "range_in_packed", "query_combined")

# files scanned for stray block_until_ready (bench code lives under
# benchmarks/ and is out of scope by construction); the Pallas-kernel
# scan follows the same list unless overridden
SYNC_SCAN = ["veneur_tpu"]

_HOST_CONVERTERS = ("float", "int", "bool")
_NP_CONVERTERS = ("numpy.asarray", "numpy.array")
_SYNC_METHODS = ("item", "tolist")


def _is_tainted(node: ast.AST, ctx: FileContext,
                tainted: Set[str]) -> bool:
    """Does this expression derive from a device value?"""
    if isinstance(node, ast.Name):
        return node.id in tainted
    if isinstance(node, ast.Attribute):
        # self.state (and anything hanging off it) is the device root
        if ctx.dotted(node) in ("self.state", "state"):
            return True
        return _is_tainted(node.value, ctx, tainted)
    if isinstance(node, ast.Subscript):
        return _is_tainted(node.value, ctx, tainted)
    if isinstance(node, ast.Call):
        fn = node.func
        resolved = ctx.resolve(fn)
        if resolved and (resolved.startswith("jax.numpy.")
                         or resolved.startswith("jax.")):
            return True
        # method call on a tainted object stays tainted
        # (state.hll.at[...].max(rows), self._ingest(self.state, ...))
        if isinstance(fn, ast.Attribute) \
                and _is_tainted(fn.value, ctx, tainted):
            return True
        return any(_is_tainted(a, ctx, tainted) for a in node.args)
    if isinstance(node, ast.BinOp):
        return (_is_tainted(node.left, ctx, tainted)
                or _is_tainted(node.right, ctx, tainted))
    if isinstance(node, (ast.Compare,)):
        return (_is_tainted(node.left, ctx, tainted)
                or any(_is_tainted(c, ctx, tainted)
                       for c in node.comparators))
    if isinstance(node, ast.UnaryOp):
        return _is_tainted(node.operand, ctx, tainted)
    if isinstance(node, (ast.Tuple, ast.List)):
        return any(_is_tainted(e, ctx, tainted) for e in node.elts)
    return False


def _check_hot_fn(ctx: FileContext, fn) -> List[Finding]:
    findings: List[Finding] = []
    tainted: Set[str] = set()
    # a parameter literally named `state` is device state by convention
    for arg in fn.args.args:
        if arg.arg == "state":
            tainted.add("state")

    for node in ast.walk(fn):
        # grow the taint set: locals assigned from device expressions
        if isinstance(node, ast.Assign) \
                and _is_tainted(node.value, ctx, tainted):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    tainted.add(t.id)
        elif isinstance(node, (ast.If, ast.While)) \
                and _is_tainted(node.test, ctx, tainted):
            findings.append(Finding(
                NAME, ctx.rel, node.lineno,
                f"Python branch on a traced/device value in hot "
                f"function {fn.name}() — forces a blocking "
                "device->host sync per batch; compute the predicate "
                "on host state or inside the jitted step"))
        elif isinstance(node, ast.Call):
            fname = node.func
            resolved = ctx.resolve(fname)
            if resolved in _HOST_CONVERTERS and len(node.args) >= 1 \
                    and _is_tainted(node.args[0], ctx, tainted):
                findings.append(Finding(
                    NAME, ctx.rel, node.lineno,
                    f"`{resolved}()` on a device value in hot function "
                    f"{fn.name}() — implicit blocking transfer"))
            elif resolved in _NP_CONVERTERS and node.args \
                    and _is_tainted(node.args[0], ctx, tainted):
                findings.append(Finding(
                    NAME, ctx.rel, node.lineno,
                    f"`{resolved.replace('numpy', 'np')}` on a device "
                    f"value in hot function {fn.name}() — full "
                    "device->host materialization blocks on every "
                    "queued step; keep the merge on device"))
            elif isinstance(fname, ast.Attribute) \
                    and fname.attr in _SYNC_METHODS \
                    and _is_tainted(fname.value, ctx, tainted):
                findings.append(Finding(
                    NAME, ctx.rel, node.lineno,
                    f"`.{fname.attr}()` on a device value in hot "
                    f"function {fn.name}() — implicit blocking "
                    "transfer"))
    return findings


def _check_jit_decls(project: Project,
                     donating: Dict[str, List[str]]) -> List[Finding]:
    findings: List[Finding] = []
    for rel, names in donating.items():
        ctx = project.file(rel)
        if ctx is None:
            findings.append(Finding(
                NAME, rel, 0, "file missing — update DONATING_JITS"))
            continue
        seen = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id in names:
                        seen[t.id] = node
        for name in names:
            node = seen.get(name)
            if node is None:
                findings.append(Finding(
                    NAME, rel, 0,
                    f"donating jit wrapper {name} not found — renamed? "
                    "update DONATING_JITS in veneur_tpu/analysis/"
                    "jax_hot_path.py"))
                continue
            donates = any(
                kw.arg in ("donate_argnums", "donate_argnames")
                for call in ast.walk(node.value)
                if isinstance(call, ast.Call)
                for kw in call.keywords)
            if not donates:
                findings.append(Finding(
                    NAME, rel, node.lineno,
                    f"{name} lost its donate_argnums/donate_argnames — "
                    "the packed feed's in-place DeviceState update "
                    "becomes a full copy per step"))
    return findings


def _check_static_args(ctx: FileContext) -> List[Finding]:
    findings: List[Finding] = []
    unhashable = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                  ast.DictComp, ast.SetComp)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        resolved = ctx.resolve(node.func)
        leaf = (resolved or "").rsplit(".", 1)[-1]
        if leaf not in JITTED_CALLEES:
            continue
        for kw in node.keywords:
            if kw.arg in STATIC_ARG_NAMES \
                    and isinstance(kw.value, unhashable):
                findings.append(Finding(
                    NAME, ctx.rel, node.lineno,
                    f"{leaf}({kw.arg}=...) passes an unhashable "
                    f"{type(kw.value).__name__.lower()} literal for a "
                    "static jit arg — TypeError at trace time; pass a "
                    "hashable (tuple/NamedTuple) spec"))
    return findings


def _check_block_until_ready(ctx: FileContext) -> List[Finding]:
    findings = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "block_until_ready":
            findings.append(Finding(
                NAME, ctx.rel, node.lineno,
                "block_until_ready outside bench code — a deliberate "
                "full-device drain; if intended, suppress with a "
                "reason"))
    return findings


def _kernel_def(ctx: FileContext, call: ast.Call):
    """Resolve `pl.pallas_call(<kernel>, ...)`'s first positional arg
    to a FunctionDef in this file. Handles a bare name and the
    `functools.partial(name, ...)` static-binding idiom; anything else
    (lambda, attribute on another module) is skipped — kernels in this
    codebase are always file-local by construction."""
    if not call.args:
        return None
    target = call.args[0]
    if isinstance(target, ast.Call):
        resolved = ctx.resolve(target.func)
        if (resolved or "").rsplit(".", 1)[-1] == "partial" \
                and target.args:
            target = target.args[0]
    if not isinstance(target, ast.Name):
        return None
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.FunctionDef) and node.name == target.id:
            return node
    return None


def _check_kernel_body(ctx: FileContext, fn) -> List[Finding]:
    """Treat a pallas_call body as device code: every positional param
    is a Ref, so the _is_tainted walk starts fully tainted. Keyword-only
    params are host statics bound via functools.partial (Python `for`
    over them unrolls at trace time and is fine; only `if`/`while` on
    Ref-derived values are syncs-in-disguise)."""
    findings: List[Finding] = []
    tainted: Set[str] = set()
    for arg in list(fn.args.posonlyargs) + list(fn.args.args):
        tainted.add(arg.arg)
    if fn.args.vararg is not None:
        tainted.add(fn.args.vararg.arg)

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) \
                and _is_tainted(node.value, ctx, tainted):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    tainted.add(t.id)
        elif isinstance(node, (ast.If, ast.While)) \
                and _is_tainted(node.test, ctx, tainted):
            kind = "if" if isinstance(node, ast.If) else "while"
            findings.append(Finding(
                NAME, ctx.rel, node.lineno,
                f"Python `{kind}` on a Ref-derived value inside Pallas "
                f"kernel {fn.name}() — kernels trace once; use "
                "@pl.when / lax.cond / lax.fori_loop for data-dependent "
                "control flow"))
        elif isinstance(node, ast.Call):
            fname = node.func
            resolved = ctx.resolve(fname)
            if resolved in _HOST_CONVERTERS and len(node.args) >= 1 \
                    and _is_tainted(node.args[0], ctx, tainted):
                findings.append(Finding(
                    NAME, ctx.rel, node.lineno,
                    f"`{resolved}()` on a Ref-derived value inside "
                    f"Pallas kernel {fn.name}() — host conversion in "
                    "device code fails on TPU (and silently diverges "
                    "in interpret mode)"))
            elif resolved in _NP_CONVERTERS and node.args \
                    and _is_tainted(node.args[0], ctx, tainted):
                findings.append(Finding(
                    NAME, ctx.rel, node.lineno,
                    f"`{resolved.replace('numpy', 'np')}` on a "
                    f"Ref-derived value inside Pallas kernel "
                    f"{fn.name}() — host materialization in device "
                    "code; keep the computation in jnp"))
            elif isinstance(fname, ast.Attribute) \
                    and fname.attr in _SYNC_METHODS \
                    and _is_tainted(fname.value, ctx, tainted):
                findings.append(Finding(
                    NAME, ctx.rel, node.lineno,
                    f"`.{fname.attr}()` on a Ref-derived value inside "
                    f"Pallas kernel {fn.name}() — host sync in device "
                    "code"))
    return findings


def _check_pallas_kernels(ctx: FileContext) -> List[Finding]:
    findings: List[Finding] = []
    checked = set()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        resolved = ctx.resolve(node.func)
        if (resolved or "").rsplit(".", 1)[-1] != "pallas_call":
            continue
        kernel = _kernel_def(ctx, node)
        if kernel is None or id(kernel) in checked:
            continue
        checked.add(id(kernel))
        findings.extend(_check_kernel_body(ctx, kernel))
    return findings


# lax collectives and the positional index of their axis-name argument;
# axis_index takes it first, the reducers/permuters take it second
_COLLECTIVE_AXIS_ARG = {
    "psum": 1, "pmax": 1, "pmin": 1, "pmean": 1, "all_gather": 1,
    "all_to_all": 1, "psum_scatter": 1, "ppermute": 1, "axis_index": 0,
}


def _axis_arg_ok(axis: ast.AST) -> bool:
    """A collective axis must be NAMED: a string literal, a variable /
    attribute holding one (REPLICA_AXIS), or a tuple of those. A
    numeric literal is a positional-array-axis footgun."""
    if isinstance(axis, ast.Constant):
        return isinstance(axis.value, str)
    if isinstance(axis, (ast.Name, ast.Attribute)):
        return True
    if isinstance(axis, (ast.Tuple, ast.List)):
        return bool(axis.elts) and all(_axis_arg_ok(e) for e in axis.elts)
    return False


def _check_collective_axes(ctx: FileContext, fn) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        resolved = ctx.resolve(node.func)
        leaf = (resolved or "").rsplit(".", 1)[-1]
        idx = _COLLECTIVE_AXIS_ARG.get(leaf)
        if idx is None:
            continue
        axis = None
        if len(node.args) > idx:
            axis = node.args[idx]
        else:
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis"):
                    axis = kw.value
                    break
        if axis is None:
            findings.append(Finding(
                NAME, ctx.rel, node.lineno,
                f"`{leaf}` inside shard_map body {fn.name}() names no "
                "mesh axis — shard_map bodies have no implicit axis "
                "context; pass the axis name (REPLICA_AXIS/SHARD_AXIS)"))
        elif not _axis_arg_ok(axis):
            findings.append(Finding(
                NAME, ctx.rel, node.lineno,
                f"`{leaf}` inside shard_map body {fn.name}() passes a "
                "non-name axis argument — a numeric axis means a "
                "positional array axis, reducing WITHIN one shard "
                "instead of across the mesh; use the mesh axis name"))
    return findings


def _check_shard_map_body(ctx: FileContext, fn) -> List[Finding]:
    """A shard_map body is device code: every positional param is a
    per-tile shard, so the kernel taint walk applies verbatim — plus
    the named-collective-axis rule."""
    findings: List[Finding] = []
    tainted: Set[str] = set()
    for arg in list(fn.args.posonlyargs) + list(fn.args.args):
        tainted.add(arg.arg)
    if fn.args.vararg is not None:
        tainted.add(fn.args.vararg.arg)

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) \
                and _is_tainted(node.value, ctx, tainted):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    tainted.add(t.id)
        elif isinstance(node, (ast.If, ast.While)) \
                and _is_tainted(node.test, ctx, tainted):
            kind = "if" if isinstance(node, ast.If) else "while"
            findings.append(Finding(
                NAME, ctx.rel, node.lineno,
                f"Python `{kind}` on a device value inside shard_map "
                f"body {fn.name}() — the body traces once per tile; "
                "use lax.cond / lax.fori_loop for data-dependent "
                "control flow"))
        elif isinstance(node, ast.Call):
            fname = node.func
            resolved = ctx.resolve(fname)
            if resolved in _HOST_CONVERTERS and len(node.args) >= 1 \
                    and _is_tainted(node.args[0], ctx, tainted):
                findings.append(Finding(
                    NAME, ctx.rel, node.lineno,
                    f"`{resolved}()` on a device value inside shard_map "
                    f"body {fn.name}() — host conversion in device "
                    "code fails at trace time"))
            elif resolved in _NP_CONVERTERS and node.args \
                    and _is_tainted(node.args[0], ctx, tainted):
                findings.append(Finding(
                    NAME, ctx.rel, node.lineno,
                    f"`{resolved.replace('numpy', 'np')}` on a device "
                    f"value inside shard_map body {fn.name}() — host "
                    "materialization in device code; keep the merge "
                    "in jnp"))
            elif isinstance(fname, ast.Attribute) \
                    and fname.attr in _SYNC_METHODS \
                    and _is_tainted(fname.value, ctx, tainted):
                findings.append(Finding(
                    NAME, ctx.rel, node.lineno,
                    f"`.{fname.attr}()` on a device value inside "
                    f"shard_map body {fn.name}() — host sync in device "
                    "code"))
    findings.extend(_check_collective_axes(ctx, fn))
    return findings


def _check_shard_maps(ctx: FileContext) -> List[Finding]:
    findings: List[Finding] = []
    checked = set()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        resolved = ctx.resolve(node.func)
        if (resolved or "").rsplit(".", 1)[-1] != "shard_map":
            continue
        # same call-site resolution as kernels: a bare name or
        # functools.partial(name, ...) defined anywhere in this file
        body = _kernel_def(ctx, node)
        if body is None or id(body) in checked:
            continue
        checked.add(id(body))
        findings.extend(_check_shard_map_body(ctx, body))
    return findings


def run(project: Project, hot_funcs: Dict[str, List[str]] = None,
        donating_jits: Dict[str, List[str]] = None,
        sync_scan: List[str] = None,
        pallas_scan: List[str] = None,
        shard_map_scan: List[str] = None) -> List[Finding]:
    findings: List[Finding] = []
    for rel, funcs in (hot_funcs if hot_funcs is not None
                       else HOT_FUNCS).items():
        ctx = project.file(rel)
        if ctx is None:
            findings.append(Finding(
                NAME, rel, 0, "file missing — update HOT_FUNCS"))
            continue
        seen = set()
        for node in ast.walk(ctx.tree):
            if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name in funcs):
                seen.add(node.name)
                findings.extend(_check_hot_fn(ctx, node))
        for name in funcs:
            if name not in seen:
                findings.append(Finding(
                    NAME, rel, 0,
                    f"hot function {name}() not found — renamed? "
                    "update HOT_FUNCS in veneur_tpu/analysis/"
                    "jax_hot_path.py"))
        findings.extend(_check_static_args(ctx))
    findings.extend(_check_jit_decls(
        project, donating_jits if donating_jits is not None
        else DONATING_JITS))
    scan = sync_scan if sync_scan is not None else SYNC_SCAN
    for ctx in project.files(*scan):
        findings.extend(_check_block_until_ready(ctx))
    for ctx in project.files(*(pallas_scan if pallas_scan is not None
                               else scan)):
        findings.extend(_check_pallas_kernels(ctx))
    for ctx in project.files(*(shard_map_scan if shard_map_scan is not None
                               else scan)):
        findings.extend(_check_shard_maps(ctx))
    return findings
