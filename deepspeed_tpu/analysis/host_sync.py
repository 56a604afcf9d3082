"""Hot-path host-sync detector: AST rule + trace-time runtime check.

A single stray ``float()`` / ``.item()`` / ``np.asarray()`` /
``block_until_ready()`` on a device value inside the decode-chunk or
train-step path stalls the async dispatch queue once per step — the
difference between a pipelined hot loop and one that serializes on the host.
Two complementary views:

- **AST half** (:class:`HostSyncRule`, :func:`hot_path_sync_findings`): scans
  the declared hot-path functions (:data:`HOT_PATH_SPECS`) for sync-shaped
  calls. Deliberate syncs are *annotated*, not silent: a
  ``# lint: host-sync-ok`` marker anywhere in the enclosing statement, or in
  the comment block immediately above it, downgrades the call to an ``info``
  finding (it stays visible in the report) — the statement is the annotation
  unit, so a multi-line harvest tuple needs one marker, not one per line.
  The documented cases: the executor's TTFT-honesty syncs (one array a
  prefill: the first token, with the expert counts behind it) and
  chunk-boundary harvest (one fetch of one packed array a chunk; a
  speculative round's copies are all started before the first is read), and
  the training engine's monitor-gated ``Train/*`` event build.
- **runtime half** (:func:`trace_sync_findings`): traces the function under
  ``jax.transfer_guard("disallow")`` — a concretization
  (``.item()``/``float()`` on a tracer) or an implicit device transfer
  during trace becomes a finding instead of a silent per-dispatch stall.
"""

import ast
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

from .report import Finding, PassResult, SEVERITY_ERROR, SEVERITY_INFO

#: marker comment that declares a deliberate, documented host sync
ALLOW_MARKER = "lint: host-sync-ok"

#: attribute-call names that force a device->host sync
_SYNC_ATTRS = {"block_until_ready", "item", "copy_to_host_async", "numpy",
               "tolist"}
#: ``np.<name>(...)`` calls that materialize a device array on host
_NP_FUNCS = {"asarray", "array"}
#: builtins that concretize a device scalar (``int()`` is deliberately NOT
#: banned: hot paths legitimately wrap host ints everywhere, and a device
#: value reaching ``int()`` almost always reaches ``np.asarray``/``float``
#: first — the signal stays, the noise goes)
_SYNC_BUILTINS = {"float", "bool"}


@dataclass
class HotPathSpec:
    """One file's hot-path anchors: functions (``name`` or ``Class.method``)
    whose bodies — including every nested closure — must not host-sync
    unannotated."""
    path: str                       # repo-relative
    anchors: Tuple[str, ...]
    #: extra allowed builtin names for this spec (e.g. a file whose hot path
    #: legitimately wraps python ints)
    allow_builtins: Tuple[str, ...] = ()


#: THE declared hot paths. decode_fns builders are fully traced (zero syncs
#: expected); the executor and train_batch are host drivers whose deliberate
#: boundary syncs carry the ALLOW_MARKER annotation.
HOT_PATH_SPECS: Tuple[HotPathSpec, ...] = (
    HotPathSpec("deepspeed_tpu/inference/decode_fns.py",
                ("build_prefill", "build_prefix_prefill",
                 "build_decode_loop", "build_paged_decode_chunk")),
    HotPathSpec("deepspeed_tpu/inference/serving/executor.py",
                ("_packed_chunk", "_packed_block_chunk", "_prefill",
                 "_suffix_prefill",
                 "ChunkedDecodeExecutor._chunk_fn",
                 "ChunkedDecodeExecutor._prefill_fn",
                 "ChunkedDecodeExecutor._suffix_prefill_fn_paged",
                 "ChunkedDecodeExecutor.prefill_into_slot",
                 "ChunkedDecodeExecutor.run_chunk",
                 "ChunkedDecodeExecutor._dispatch",
                 "ChunkedDecodeExecutor._timed")),
    HotPathSpec("deepspeed_tpu/runtime/engine.py",
                ("DeepSpeedEngine._build_train_step",
                 "DeepSpeedEngine._build_train_step_quantized",
                 "DeepSpeedEngine.train_batch",
                 "DeepSpeedEngine._write_monitor_events")),
)


def _sync_call_name(node: ast.Call, allow_builtins) -> Optional[str]:
    """The banned-call label a Call node matches, or None."""
    fn = node.func
    if isinstance(fn, ast.Attribute):
        if fn.attr in _SYNC_ATTRS:
            return f".{fn.attr}()"
        if fn.attr in _NP_FUNCS and isinstance(fn.value, ast.Name) \
                and fn.value.id in ("np", "numpy", "onp"):
            return f"{fn.value.id}.{fn.attr}()"
    elif isinstance(fn, ast.Name):
        if fn.id in _SYNC_BUILTINS and fn.id not in allow_builtins:
            # float()/int() over a literal or pure-host expression is noise;
            # only constant args are provably host-only at the AST level
            if not all(isinstance(a, ast.Constant) for a in node.args):
                return f"{fn.id}()"
    return None


def _anchor_functions(tree: ast.Module, anchors: Sequence[str]):
    """Yield ``(qualname, FunctionDef)`` for each anchor present in the
    module (top-level functions and single-level ``Class.method``)."""
    wanted = set(anchors)
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in wanted:
            yield node.name, node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = f"{node.name}.{sub.name}"
                    if qual in wanted:
                        yield qual, sub


def _stmt_span(fn: ast.AST, lineno: int) -> Tuple[int, int]:
    """Line span of the innermost statement containing ``lineno`` (the
    annotation unit: a multi-line statement is annotated as a whole)."""
    best = None
    for node in ast.walk(fn):
        if isinstance(node, ast.stmt) and node.lineno <= lineno \
                <= (node.end_lineno or node.lineno):
            if best is None or node.lineno >= best[0]:
                best = (node.lineno, node.end_lineno or node.lineno)
    return best or (lineno, lineno)


def _annotated(source_lines: List[str], fn: ast.AST, lineno: int) -> bool:
    """True when the enclosing statement — any of its lines, or the
    contiguous comment block immediately above it — carries the allow
    marker."""
    start, end = _stmt_span(fn, lineno)
    for ln in range(start, min(end, len(source_lines)) + 1):
        if ALLOW_MARKER in source_lines[ln - 1]:
            return True
    ln = start - 1
    while ln >= 1 and source_lines[ln - 1].lstrip().startswith("#"):
        if ALLOW_MARKER in source_lines[ln - 1]:
            return True
        ln -= 1
    return False


def _spec_findings(spec: HotPathSpec, tree: ast.Module,
                   source_lines: List[str]) -> Tuple[List[Finding], int]:
    """Scan one parsed file against one spec; returns ``(findings,
    n_anchors_checked)``."""
    findings: List[Finding] = []
    anchors = dict(_anchor_functions(tree, spec.anchors))
    for missing in set(spec.anchors) - set(anchors):
        findings.append(Finding(
            "host_sync", SEVERITY_ERROR, f"{spec.path}:{missing}",
            f"declared hot-path anchor {missing!r} no longer exists — "
            "update analysis.host_sync.HOT_PATH_SPECS"))
    for qual, fn in anchors.items():
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            label = _sync_call_name(node, spec.allow_builtins)
            if label is None:
                continue
            site = f"{spec.path}:{node.lineno} ({qual})"
            if _annotated(source_lines, fn, node.lineno):
                findings.append(Finding(
                    "host_sync", SEVERITY_INFO, site,
                    f"annotated host sync {label} (documented exception)",
                    {"call": label, "qualname": qual}))
            else:
                findings.append(Finding(
                    "host_sync", SEVERITY_ERROR, site,
                    f"host sync {label} on the hot path — stalls the "
                    "async dispatch queue every step; hoist it out or "
                    f"annotate the line with '# {ALLOW_MARKER} (why)'",
                    {"call": label, "qualname": qual}))
    return findings, len(anchors)


def hot_path_sync_findings(repo_root: str,
                           specs: Sequence[HotPathSpec] = HOT_PATH_SPECS
                           ) -> PassResult:
    """Run the AST half over every declared hot path (missing anchors are
    errors — this entry must run even when the files are unchanged, so spec
    rot is caught)."""
    import os
    result = PassResult("host_sync", "hot-paths", checked=0)
    for spec in specs:
        path = os.path.join(repo_root, spec.path)
        with open(path) as f:
            source = f.read()
        tree = ast.parse(source, filename=path)
        findings, n_anchors = _spec_findings(spec, tree, source.splitlines())
        result.findings.extend(findings)
        result.checked += n_anchors
    return result


class HostSyncRule:
    """The same check as an ``AstRule`` for :func:`run_ast_rules` — files
    outside the declared specs contribute nothing. Note the spec-driven
    entry (:func:`hot_path_sync_findings`) is still what the full sweep
    runs: a rule sweep restricted to changed files would never notice a
    spec whose file was deleted."""

    name = "host_sync"

    def __init__(self, specs: Sequence[HotPathSpec] = HOT_PATH_SPECS):
        self.specs = specs

    def check(self, tree: ast.Module, source_lines: List[str],
              relpath: str) -> List[Finding]:
        findings: List[Finding] = []
        for spec in self.specs:
            if spec.path == relpath:
                findings.extend(_spec_findings(spec, tree, source_lines)[0])
        return findings


def trace_sync_findings(fn: Callable, args: Tuple[Any, ...],
                        target: str = "trace") -> PassResult:
    """Runtime half: trace ``fn(*args)`` under a transfer guard.

    A host sync written against a *traced* value concretizes — ``.item()`` /
    ``float()`` raise ``ConcretizationTypeError``, ``np.asarray()`` raises
    ``TracerArrayConversionError``, ``bool()`` its boolean sibling — so the
    injected-sync-in-a-chunk-body regression is caught deterministically at
    trace time, before it ever ships a per-dispatch stall. The transfer
    guard is belt-and-braces on top: any *implicit* device transfer the
    trace performs (a fresh host constant pushed per-dispatch) also fails.
    """
    import jax
    tracer_errors = tuple(
        e for e in (getattr(jax.errors, n, None)
                    for n in ("ConcretizationTypeError",
                              "TracerArrayConversionError",
                              "TracerBoolConversionError",
                              "TracerIntegerConversionError"))
        if e is not None)
    result = PassResult("host_sync_trace", target, checked=1)
    try:
        with jax.transfer_guard("disallow"):
            jax.make_jaxpr(fn)(*args)
    except tracer_errors as e:
        result.findings.append(Finding(
            "host_sync_trace", SEVERITY_ERROR, target,
            "traced value concretized during trace (float()/.item()/"
            "np.asarray() on a tracer) — this would host-sync every dispatch",
            {"error": str(e).splitlines()[0]}))
    except Exception as e:  # transfer guard violations are XlaRuntimeError
        # only the guard's own message shape is a finding ("Disallowed
        # host-to-device transfer ..."); any other exception — even one that
        # happens to mention "transfer" — is a real trace failure and must
        # propagate with its traceback, not be re-diagnosed
        msg = str(e)
        if "Disallowed" not in msg or "transfer" not in msg.lower():
            raise
        result.findings.append(Finding(
            "host_sync_trace", SEVERITY_ERROR, target,
            "implicit device transfer during trace (host constant pushed "
            "per-dispatch)", {"error": msg.splitlines()[0][:200]}))
    return result
