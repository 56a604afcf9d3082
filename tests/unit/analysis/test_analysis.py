"""Program-contract analyzer suite (ISSUE 11): one positive and one
seeded-negative lane per pass.

The negative controls are the point: every pass must catch its deliberately
broken program — a donation that silently copies, a weak-type-drift retrace,
an injected ``.item()`` in a chunk body, a dequant traced inside the loop,
a collective site that under-records its bytes. A lint that cannot fail its
seeded regression is a lint that is not running.

The real-program acceptance lanes (donation + retrace against the actual
``ChunkedDecodeExecutor`` and quantized train step) run the same sweep lanes
``bin/ds-tpu-lint`` ships, so the CI property and the CLI property cannot
drift apart.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.analysis import (BareAssertRule, CompileCacheLint,
                                    DonationError, EmissionTagRule, Finding,
                                    LoopInvarianceError, Report,
                                    assert_all_donated, assert_loop_invariant,
                                    cache_compile_counts,
                                    crosscheck_findings, donation_findings,
                                    hot_path_sync_findings, loop_body_findings,
                                    run_ast_rules, trace_sync_findings)
from deepspeed_tpu.analysis.host_sync import HotPathSpec
from deepspeed_tpu.analysis.report import PassResult

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

_INT8 = lambda a: getattr(a, "dtype", None) == jnp.int8  # noqa: E731


# ------------------------------------------------------------------- report
def test_report_json_schema():
    rep = Report()
    r = PassResult("donation", "toy", checked=3)
    r.findings.append(Finding("donation", "error", "toy/x", "not aliased"))
    r.findings.append(Finding("donation", "info", "toy/y", "allowlisted"))
    rep.add(r)
    d = rep.to_dict()
    assert d["version"] == 1 and d["ok"] is False and d["n_errors"] == 1
    assert d["passes"][0]["checked"] == 3
    f = d["passes"][0]["findings"][0]
    assert set(f) == {"pass", "severity", "site", "message", "details"}
    with pytest.raises(ValueError, match="severity"):
        Finding("x", "fatal", "s", "m")


# ----------------------------------------------------------------- donation
def test_donation_positive_and_seeded_copy():
    def good(x, y):
        return x + y, y * 2

    args = (jnp.ones((4, 4)), jnp.ones((4, 4)))
    res = assert_all_donated(good, args, donate_argnums=(0,), target="good")
    assert res.checked == 1 and not res.findings

    # seeded negative: the donated fp32 buffer cannot alias the fp16 output
    # — XLA falls back to a silent copy, which the audit must surface
    def copy_fallback(x, y):
        return (x.astype(jnp.float16) + y.astype(jnp.float16),)

    res = donation_findings(copy_fallback, args, donate_argnums=(0,),
                            target="bad")
    errs = [f for f in res.findings if f.severity == "error"]
    assert len(errs) == 1 and "NOT aliased" in errs[0].message
    with pytest.raises(DonationError, match="silent copy"):
        assert_all_donated(copy_fallback, args, donate_argnums=(0,))

    # the allowlist downgrades a DECLARED non-donation to an info finding
    res = donation_findings(copy_fallback, args, donate_argnums=(0,),
                            allow=(r"re:^\[0\]",), target="allowed")
    assert not [f for f in res.findings if f.severity == "error"]
    assert any(f.severity == "info" and "allowlisted" in f.message
               for f in res.findings)


def test_donation_unused_arg_is_warning_not_error():
    def unused(x, y):
        return (y * 2,)

    res = donation_findings(unused, (jnp.ones((3,)), jnp.ones((3,))),
                            donate_argnums=(0,), target="unused")
    assert [f.severity for f in res.findings] == ["warning"]
    assert "unused" in res.findings[0].message


# ------------------------------------------------------------------ retrace
def test_retrace_lint_positive_and_weak_type_drift():
    fns = {}

    def f(x, n):
        return x * n

    fns["toy"] = jax.jit(f)
    x = jnp.ones((4,), jnp.int32)
    fns["toy"](x, jnp.int32(3))
    lint = CompileCacheLint(fns, target="toy-cache")
    lint.snapshot()
    fns["toy"](x, jnp.int32(4))            # same types: cached
    assert not lint.findings().findings
    # seeded negative: a python int is WEAKLY typed — jax re-traces the same
    # shapes under weak-type promotion, the classic silent second compile
    fns["toy"](x, 3)
    res = lint.findings()
    errs = [f for f in res.findings if f.severity == "error"]
    assert errs and "compiled 2x" in errs[0].message
    assert cache_compile_counts(fns)["toy"] == 2


def test_retrace_lint_flags_new_key_after_snapshot():
    """Drift usually mints a NEW (slots, cap, chunk, ...) cache key rather
    than retracing an old one — a key born after the warmup snapshot is the
    same contract breach and must fail the lint."""
    fns = {"warm": jax.jit(lambda x: x + 1)}
    fns["warm"](jnp.ones((2,)))
    lint = CompileCacheLint(fns, target="drift")
    lint.snapshot()
    assert not lint.findings().findings
    fns["drifted"] = jax.jit(lambda x: x * 2)      # a new key appears...
    fns["drifted"](jnp.ones((3,)))                 # ...and compiles
    errs = [f for f in lint.findings().findings if f.severity == "error"]
    assert len(errs) == 1 and "NEW cache key" in errs[0].message


def test_retrace_lint_walks_tuple_entries_and_empty_cache():
    fns = {"pair": (jax.jit(lambda x: x + 1), jax.jit(lambda x: x * 2))}
    fns["pair"][0](jnp.ones((2,)))
    counts = cache_compile_counts(fns)
    assert counts == {"pair[0]": 1, "pair[1]": 0}
    empty = CompileCacheLint({}, target="empty").findings()
    assert [f.severity for f in empty.findings] == ["warning"]


# ---------------------------------------------------------------- host sync
def test_host_sync_ast_catches_injected_item(tmp_path):
    bad = tmp_path / "hot.py"
    bad.write_text(
        "import numpy as np\n"
        "def chunk_body(fn, args, pool):\n"
        "    out = fn(*args)\n"
        "    peek = out[0].item()\n"                       # the injection
        "    # lint: host-sync-ok (chunk-boundary harvest)\n"
        "    host = np.asarray(out[1])\n"
        "    return peek, host\n")
    spec = HotPathSpec("hot.py", ("chunk_body",))
    res = hot_path_sync_findings(str(tmp_path), (spec,))
    errs = [f for f in res.findings if f.severity == "error"]
    infos = [f for f in res.findings if f.severity == "info"]
    assert len(errs) == 1 and ".item()" in errs[0].message
    assert len(infos) == 1 and "np.asarray" in infos[0].message


def test_host_sync_ast_flags_vanished_anchor(tmp_path):
    (tmp_path / "hot.py").write_text("def other():\n    pass\n")
    res = hot_path_sync_findings(
        str(tmp_path), (HotPathSpec("hot.py", ("chunk_body",)),))
    assert any("no longer exists" in f.message for f in res.findings)


def test_host_sync_rule_runs_under_shared_runner(tmp_path):
    """HostSyncRule is a real AstRule: the shared runner drives it next to
    the bare-assert rule — files outside the specs contribute nothing."""
    from deepspeed_tpu.analysis import HostSyncRule
    (tmp_path / "hot.py").write_text(
        "def chunk_body(fn, args):\n    return fn(*args).item()\n")
    (tmp_path / "cold.py").write_text(
        "def helper(x):\n    return x.item()\n")       # not a declared path
    rule = HostSyncRule((HotPathSpec("hot.py", ("chunk_body",)),))
    res = run_ast_rules(str(tmp_path), [rule, BareAssertRule()],
                        paths=("hot.py", "cold.py"))
    errs = [f for f in res.findings if f.severity == "error"]
    assert len(errs) == 1 and ".item()" in errs[0].message
    assert errs[0].site.startswith("hot.py:")


def test_host_sync_repo_hot_paths_clean():
    """The declared hot paths carry only ANNOTATED syncs (the TTFT/harvest/
    monitor-gated exceptions) — zero unannotated sync calls."""
    res = hot_path_sync_findings(REPO)
    errs = [f for f in res.findings if f.severity == "error"]
    assert errs == [], [str(f) for f in errs]
    assert res.checked >= 10           # all declared anchors still exist
    # the documented exceptions remain visible as info findings
    assert any("annotated" in f.message for f in res.findings)


def test_host_sync_trace_catches_injected_sync():
    def clean(x):
        return jax.lax.fori_loop(0, 3, lambda i, c: c + x.sum(), 0.0)

    x = jnp.ones((4,))
    assert not trace_sync_findings(clean, (x,)).findings

    # the ISSUE's seeded control: an injected ``.item()`` inside a chunk-like
    # loop body — the exact shape a stray debug line ships
    def item_in_body(x):
        return jax.lax.fori_loop(
            0, 3, lambda i, c: c + x.sum().item(), 0.0)

    res = trace_sync_findings(item_in_body, (x,), target="item")
    assert [f.severity for f in res.findings] == ["error"]
    assert "concretized" in res.findings[0].message

    def np_in_body(x):
        return x * np.asarray(x).sum()                     # tracer -> numpy

    res = trace_sync_findings(np_in_body, (x,), target="np")
    assert [f.severity for f in res.findings] == ["error"]

    def float_in_body(x):
        return x * float(x.sum())                          # concretizes

    res = trace_sync_findings(float_in_body, (x,), target="float")
    assert [f.severity for f in res.findings] == ["error"]


# ----------------------------------------------------------- loop invariance
def test_loop_invariance_scan_and_while_and_vacuous_guard():
    x8 = jnp.ones((4,), jnp.int8)

    def scan_bad(x):                   # static fori_loop lowers to scan
        return jax.lax.fori_loop(0, 4,
                                 lambda i, c: c + x.astype(jnp.float32).sum(),
                                 0.0)

    with pytest.raises(LoopInvarianceError):
        assert_loop_invariant(scan_bad, (x8,), invar_predicate=_INT8)

    def while_bad(x, n):               # dynamic bound stays a while
        return jax.lax.while_loop(
            lambda s: s[0] < n,
            lambda s: (s[0] + 1, s[1] + x.astype(jnp.float32).sum()),
            (0, 0.0))

    with pytest.raises(LoopInvarianceError):
        assert_loop_invariant(while_bad, (x8, 4), invar_predicate=_INT8)

    def hoisted(x):
        xf = x.astype(jnp.float32)
        return jax.lax.fori_loop(0, 4, lambda i, c: c + xf.sum(), 0.0)

    assert assert_loop_invariant(hoisted, (x8,), invar_predicate=_INT8) == 1

    def no_loop(x):
        return x.astype(jnp.float32).sum()

    # the pin target vanishing must fail loudly, not pass vacuously
    with pytest.raises(LoopInvarianceError, match="no while/scan"):
        assert_loop_invariant(no_loop, (x8,), invar_predicate=_INT8)
    findings, n = loop_body_findings(no_loop, (x8,), invar_predicate=_INT8)
    assert findings == [] and n == 0


def test_loop_invariance_eqn_predicate():
    def loop(x):
        return jax.lax.fori_loop(0, 4, lambda i, c: c + jnp.sin(x).sum(), 0.0)

    findings, n = loop_body_findings(
        loop, (jnp.ones((4,)),),
        eqn_predicate=lambda e: e.primitive.name == "sin",
        what="sin-hoist")
    assert n == 1 and len(findings) == 1
    assert "sin" in findings[0].message


def test_loop_invariance_catches_in_body_dequant_on_chunk_fn():
    """The serving chunk body (scan-lowered fori) with an identity dequant
    traces the int8 payload INTO the body — the generalized pass must catch
    it there too, not only in the generate while_loop (the PR 5 pin's gap)."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.decode_fns import (build_paged_decode_chunk,
                                                    make_slot_select_fn)
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.serving.kv_pool import PagedKVPool
    from deepspeed_tpu.models.causal_lm import gpt2_cfg
    cfg = gpt2_cfg(vocab_size=96, max_seq_len=64, n_embd=32, n_layer=2,
                   n_head=4, dtype=jnp.float32)
    eng = InferenceEngine(cfg, DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=32,
        weight_quant={"enabled": True, "bits": 8}))
    select = make_slot_select_fn(False, 1.0, 0, 1.0)
    pool = PagedKVPool(cfg, 2, 32, page_size=8, dtype=eng.dtype)
    args = (eng.params, jnp.zeros((2, 1), jnp.int32), pool.caches,
            jnp.asarray(pool.page_table),
            jnp.full((2,), 8, jnp.int32), jnp.ones((2,), bool),
            jnp.full((2,), 5, jnp.int32), jnp.full((2,), -1, jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jax.random.PRNGKey(0))
    good = build_paged_decode_chunk(eng.module, eng._dequant, select, 3,
                                    kv_cap=32, overlap=eng.comm_overlap)
    assert assert_loop_invariant(good, args, invar_predicate=_INT8,
                                 what="dequant-hoist") >= 1
    bad = build_paged_decode_chunk(eng.module, lambda p: p, select, 3,
                                   kv_cap=32, overlap=eng.comm_overlap)
    with pytest.raises(LoopInvarianceError, match="dequant-hoist"):
        assert_loop_invariant(bad, args, invar_predicate=_INT8,
                              what="dequant-hoist")


# --------------------------------------------------------- collective schema
def test_collective_crosscheck_positive_and_seeded_miscount(eight_devices):
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.parallel import overlap as ov
    from deepspeed_tpu.parallel.mesh import AXIS_TENSOR, MeshSpec
    from deepspeed_tpu.utils import comms_logging as cl
    from deepspeed_tpu.utils.jax_compat import shard_map
    mesh = MeshSpec({"tensor": 4}, eight_devices[:4])
    specs = dict(mesh=mesh.mesh, axis_names={AXIS_TENSOR},
                 in_specs=(P(AXIS_TENSOR, None), P(None, None)),
                 out_specs=P(None, None), check_vma=False)
    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((16, 6), jnp.float32)

    def ring_twice(a, b):
        y1 = ov.chunked_allgather_matmul(a, b, AXIS_TENSOR,
                                         site="test.ring2")
        y2 = ov.chunked_allgather_matmul(a, b, AXIS_TENSOR,
                                         site="test.ring2")
        return y1 + y2

    fn = shard_map(ring_twice, **specs)
    res = crosscheck_findings(fn, (x, w), site_prefixes=("test.",),
                              target="ring")
    assert res.checked == 6              # 2 calls x (W-1) ppermutes, W=4
    assert not [f for f in res.findings if f.severity == "error"]

    # seeded negative: re-introduce the PR 3 last-call-overwrite bug — the
    # second trace of the same site OVERWRITES bytes_total instead of summing
    orig = cl.CollectiveSpans.record

    def overwrite(self, site, comm_op, size_bytes, n_ranks, overlapped):
        orig(self, site, comm_op, size_bytes, n_ranks, overlapped)
        self._spans[site]["bytes_total"] = int(size_bytes)

    cl.CollectiveSpans.record = overwrite
    try:
        res = crosscheck_findings(fn, (x, w), site_prefixes=("test.",),
                                  target="ring-bug")
    finally:
        cl.CollectiveSpans.record = orig
    errs = [f for f in res.findings if f.severity == "error"]
    assert len(errs) == 1 and "mismatch" in errs[0].message
    assert errs[0].details["modeled"] > errs[0].details["recorded"]


def test_collective_accounting_reduce_scatter_and_psum(eight_devices):
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.analysis import collective_accounting
    from deepspeed_tpu.parallel.mesh import MeshSpec
    from deepspeed_tpu.utils.jax_compat import shard_map
    mesh = MeshSpec({"tensor": 4}, eight_devices[:4])

    def coll(x):
        a = jax.lax.psum(x, "tensor")
        b = jax.lax.psum_scatter(x, "tensor", scatter_dimension=0,
                                 tiled=True)
        return a, b

    fn = shard_map(coll, mesh=mesh.mesh, axis_names={"tensor"},
                   in_specs=(P(None, None),),
                   out_specs=(P(None, None), P("tensor", None)),
                   check_vma=False)
    recs = collective_accounting(fn, (jnp.ones((8, 4), jnp.float32),))
    by_prim = {r["primitive"]: r for r in recs}
    nbytes = 8 * 4 * 4
    # ring allreduce: 2(W-1)/W x payload; reduce-scatter: (W-1) x shard out
    assert by_prim["psum"]["wire_bytes"] == int(2 * 3 * nbytes / 4)
    assert by_prim["reduce_scatter"]["wire_bytes"] == 3 * (nbytes // 4)


# ---------------------------------------------------------------- AST rules
def test_bare_assert_rule_catches_and_repo_is_clean(tmp_path):
    pkg = tmp_path / "deepspeed_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "def f(x):\n    assert x > 0, 'positive'\n    return x\n")
    res = run_ast_rules(str(tmp_path), [BareAssertRule()])
    assert len(res.findings) == 1
    assert "python -O" in res.findings[0].message
    assert res.findings[0].site == "deepspeed_tpu/mod.py:2"

    # the acceptance property: ZERO bare asserts across the real library
    res = run_ast_rules(REPO, [BareAssertRule()])
    assert res.checked > 150
    assert res.findings == [], [str(f) for f in res.findings]


def test_emission_tag_rule_under_runner(tmp_path):
    from deepspeed_tpu.observability import schema
    mod = tmp_path / "emitter.py"
    mod.write_text(
        "def publish(mon, v):\n"
        "    mon.write_events([('serving/ttft_ms', v, 0),\n"
        "                      ('serving/not_a_real_tag', v, 0)])\n")
    rule = EmissionTagRule(schema.resolve, ("emitter.py",))
    res = run_ast_rules(str(tmp_path), [rule], paths=("emitter.py",))
    assert len(res.findings) == 1
    assert "serving/not_a_real_tag" in res.findings[0].message

    # the migrated schema-facing API still reports the same shape
    problems = schema.lint_emission_sites(REPO)
    assert problems == []


def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    pkg = tmp_path / "deepspeed_tpu"
    pkg.mkdir()
    (pkg / "broken.py").write_text("def f(:\n")
    res = run_ast_rules(str(tmp_path), [BareAssertRule()])
    assert any("syntax error" in f.message for f in res.findings)


# ----------------------------------------------- real-program acceptance lanes
@pytest.mark.parametrize("lane_name", ["serving_lane", "train_lane",
                                       "overlap_lane"])
def test_sweep_lane_runs_clean_on_real_programs(lane_name, eight_devices):
    """The acceptance lanes: donation + retrace against the REAL
    ``ChunkedDecodeExecutor`` (one-compile-per-key across a repeated
    workload) and the REAL quantized train step, the dequant-hoist pin on
    both decode bodies, and the ring byte cross-check — exactly the lanes
    ``bin/ds-tpu-lint`` ships (shared code, no drift)."""
    from deepspeed_tpu.analysis import sweep
    report = Report()
    getattr(sweep, lane_name)(report)
    errors = report.findings("error")
    assert errors == [], [str(f) for f in errors]
    names = {r.name for r in report.results}
    if lane_name == "serving_lane":
        assert {"retrace", "donation", "loop_invariance",
                "host_sync_trace"} <= names
        donation = {r.target: r.checked for r in report.results
                    if r.name == "donation"}
        # the chunk and the two prefills the cells run (a miss's, handed the
        # last miss's batch-1 cache, and a prefix hit's, handed the pool), and
        # the pool's donated movers
        assert set(donation) == {"serve_chunk", "serve_prefill",
                                 "serve_suffix_prefill", "kv_pool.scatter",
                                 "kv_pool.cow", "kv_pool.state_zero_fill"}
        assert sum(donation.values()) >= 12 and min(donation.values()) >= 1
        assert donation["serve_prefill"] == donation["serve_chunk"]
    elif lane_name == "train_lane":
        assert {"retrace", "donation"} <= names
        don = next(r for r in report.results if r.name == "donation")
        assert don.checked > 50            # state tree + EF residual leaves
    else:
        assert names == {"collective_schema"}
        assert sum(r.checked for r in report.results) >= 10


def test_changed_files_includes_untracked(tmp_path):
    """``--changed-only`` must lint brand-new modules too — a pre-commit run
    that skips untracked files skips exactly the files being committed."""
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True,
                   capture_output=True)
    pkg = tmp_path / "deepspeed_tpu"
    pkg.mkdir()
    (pkg / "tracked.py").write_text("x = 1\n")
    subprocess.run(["git", "-C", str(tmp_path), "add", "-A"], check=True,
                   capture_output=True)
    subprocess.run(["git", "-C", str(tmp_path), "-c",
                    "user.email=t@t", "-c", "user.name=t",
                    "commit", "-qm", "init"], check=True,
                   capture_output=True)
    (pkg / "tracked.py").write_text("x = 2\n")        # modified
    (pkg / "brand_new.py").write_text("y = 1\n")      # untracked
    from deepspeed_tpu.analysis.sweep import changed_files
    got = set(changed_files(str(tmp_path)))
    assert got == {"deepspeed_tpu/tracked.py", "deepspeed_tpu/brand_new.py"}


# ----------------------------------------------------------------- CLI smoke
def test_lint_cli_ast_only_emits_valid_json(tmp_path):
    """``bin/ds-tpu-lint --ast-only --json`` runs offline on CPU, exits 0 on
    the clean tree, and emits the pinned JSON schema."""
    out = tmp_path / "lint.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds-tpu-lint"),
         "--ast-only", "--json", str(out)],
        capture_output=True, text=True, timeout=240, cwd=str(tmp_path),
        env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    data = json.loads(out.read_text())
    assert data["version"] == 1 and data["ok"] is True
    assert data["n_errors"] == 0
    pass_names = {p["name"] for p in data["passes"]}
    assert {"ast_rules", "host_sync"} <= pass_names
    for p in data["passes"]:
        assert p["checked"] > 0
        for f in p["findings"]:
            assert set(f) == {"pass", "severity", "site", "message",
                              "details"}
