"""Device scopes (``observability.scope``): the declared list and its lint,
the scopes the five compiled programs carry on the ops that cost device time,
and the proof that a scope changes nothing but metadata (ISSUE 36)."""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.observability import schema, scope
from deepspeed_tpu.observability.schema import SCOPE_PREFIX, SCOPES

import scoped_programs as sp

pytestmark = pytest.mark.observability

ROOT = sp.ROOT


# ------------------------------------------------------------------ the API
class TestScopeApi:
    def test_a_scope_lands_in_the_ops_name_with_the_prefix(self):
        def f(x):
            with scope("attn.qkv"):
                return x * 2.0

        text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
        assert f"{SCOPE_PREFIX}attn.qkv/mul" in text

    def test_a_scope_is_a_decorator_too_and_jax_adds_the_phase(self):
        @scope("loss")
        def f(x):
            return jnp.sum(x ** 2)

        text = jax.jit(jax.grad(f)).lower(jnp.ones(4)).as_text(debug_info=True)
        assert f"transpose(jvp({SCOPE_PREFIX}loss))" in text

    def test_an_undeclared_scope_raises_while_jax_traces(self):
        def f(x):
            with scope("attn.everything"):
                return x

        with pytest.raises(KeyError, match="not declared"):
            jax.jit(f).lower(jnp.ones(4))

    def test_nvtx_no_longer_offers_a_second_way(self):
        from deepspeed_tpu.utils import nvtx
        assert not hasattr(nvtx, "named_scope")
        assert "named_scope" not in (nvtx.__doc__ or "")

    def test_there_is_no_switch(self):
        """A scope costs nothing in the executed program, so nothing turns it
        off: ``scope`` takes a name and nothing else."""
        import inspect
        assert list(inspect.signature(scope).parameters) == ["name"]


# ----------------------------------------------------------------- the lint
class TestScopeLint:
    def test_every_scope_opened_is_declared_and_every_declared_one_opened(self):
        assert schema.lint_emission_sites(ROOT) == []

    def test_the_lint_walks_the_files_that_open_scopes(self):
        opened = set()
        for dirpath, _, files in os.walk(os.path.join(ROOT, "deepspeed_tpu")):
            for name in files:
                path = os.path.join(dirpath, name)
                if name.endswith(".py") and "observability" not in path:
                    with open(path) as f:
                        if re.search(r"\bscope\(\"", f.read()):
                            opened.add(os.path.relpath(path, ROOT))
        assert opened <= set(schema.SCOPE_MODULES), opened - set(schema.SCOPE_MODULES)

    @pytest.mark.parametrize("source,problem", [
        ('from ..observability import scope\nwith scope("attn.qkv"):\n    pass\n', None),
        ('with scope("attn.everything"):\n    pass\n', "attn.everything"),
        ('@scope("comm.nothing")\ndef f():\n    pass\n', "comm.nothing"),
        ('with scope(name):\n    pass\n', "None"),
    ], ids=["declared", "undeclared", "undeclared-decorator", "not-a-literal"])
    def test_the_rule_on_a_source(self, source, problem):
        import ast
        rule = schema.emission_tag_rule()
        rel = schema.SCOPE_MODULES[0]
        found = rule.check(ast.parse(source), source.splitlines(), rel)
        assert [f.details["tag"] for f in found] == ([] if problem is None else [problem])

    def test_a_declared_scope_nobody_opens_fails(self, monkeypatch):
        monkeypatch.setitem(SCOPES, "attn.unused", ("compiled steps", "nothing", "nothing"))
        assert any("attn.unused" in p for p in schema.lint_emission_sites(ROOT))

    def test_every_scope_says_its_layer_and_reader(self):
        layers = {"compiled steps", "train engine", "zero and collectives"}
        for name, (layer, holds, reads) in SCOPES.items():
            assert layer in layers and holds and reads, name
            assert re.fullmatch(r"[a-z0-9_]+(\.[a-z0-9_]+)*", name), name


# ------------------------------------------ the compile cache keys on the scopes
class TestTheCacheKeysOnTheScopes:
    """jax's persistent cache leaves op names out of its key, and a loaded
    executable carries the names it was compiled with: a program that differs
    from a cached one only in its scopes must not be loaded in its place."""

    def _module(self, tmp_path, monkeypatch, source):
        path = tmp_path / "opens_scopes.py"
        path.write_text(source)
        monkeypatch.setattr(schema, "SCOPE_MODULES", (os.path.relpath(path, ROOT),))
        return schema.scope_sites_digest()

    def test_the_digest_follows_the_call_sites_and_not_the_lines(self, tmp_path,
                                                                 monkeypatch):
        one = self._module(tmp_path, monkeypatch, 'with scope("norm"):\n    pass\n')
        moved = self._module(tmp_path, monkeypatch,
                             '\n\n# a comment\nwith scope("norm"):\n    pass\n')
        other = self._module(tmp_path, monkeypatch, 'with scope("head"):\n    pass\n')
        two = self._module(tmp_path, monkeypatch,
                           'with scope("norm"):\n    pass\nwith scope("head"):\n    pass\n')
        assert one == moved and len({one, other, two}) == 3

    def test_enable_compile_cache_adds_the_digest_to_jaxs_key(self, tmp_path, monkeypatch):
        from jax._src import cache_key

        from deepspeed_tpu.utils import device
        monkeypatch.setattr(cache_key, "custom_hook", cache_key.custom_hook)   # restored
        monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))    # placed outside: no config
        device._key_the_cache_on_the_scopes.cache_clear()
        assert device.enable_compile_cache() == str(tmp_path)
        assert cache_key.custom_hook() == "deepspeed_tpu scopes " + schema.scope_sites_digest()
        device._key_the_cache_on_the_scopes.cache_clear()


# ------------------------------------- the scopes of the five lowered programs
HEAVY = ("dot_general", "gather", "scatter", "dynamic_update_slice", "sort",
         "custom_call", "convolution")
_NO_DEVICE_WORK = ("@Sharding", "@SPMDFullToShardShape", "@SPMDShardToFullShape")
_SCOPED = re.compile(r"(?:^|[/(])" + re.escape(SCOPE_PREFIX) + r"([a-z0-9_.]+)")
_ACT_STACK = re.compile(r"while/body/dynamic_(update_)?slice$")


def heavy_ops(text: str):
    """``[(op, its own name, declared scope or None)]`` of a lowered StableHLO
    module (debug info on). A private function's ops are named relative to
    its call sites, so an op has the innermost scope of its own name or,
    failing that, the scope every call site of its function has."""
    names = dict(re.findall(r'^#loc(\d+) = loc\("([^"]*)"', text, re.M))
    loc_of = lambda line: (re.search(r"loc\(#loc(\d+)\)\s*$", line) or [None, None])[1]  # noqa: E731
    fn, ops, calls, pending = None, [], {}, []
    for line in text.splitlines():
        m = re.match(r"\s*func\.func (?:public|private) @([\w.]+)", line)
        if m:
            fn = m.group(1)
            continue
        indent = len(line) - len(line.lstrip())
        if pending and pending[-1][0] == indent and line.lstrip().startswith("})"):
            _, kind, what = pending.pop()
            name = names.get(loc_of(line), "")
            (ops if kind == "op" else calls.setdefault(what, [])).append(
                (fn, what, name) if kind == "op" else (fn, name))
            continue
        op = re.search(r'"?stablehlo\.([a-z_]+)"?[ (]', line)
        call = re.search(r"(?:func\.)?call @([\w.]+)\(", line)
        if call:
            calls.setdefault(call.group(1), []).append((fn, names.get(loc_of(line), "")))
        elif op and op.group(1) in HEAVY:
            if op.group(1) == "custom_call" and any(t in line for t in _NO_DEVICE_WORK):
                continue
            if loc_of(line) is None:
                pending.append((indent, "op", op.group(1)))
            else:
                ops.append((fn, op.group(1), names[loc_of(line)]))

    def scope_of_function(f, seen=()):
        sites = calls.get(f)
        if not sites or f in seen:
            return None
        found = {(_SCOPED.findall(name) or [None])[-1]
                 or scope_of_function(caller, seen + (f,)) for caller, name in sites}
        return found.pop() if len(found) == 1 else None

    return [(op, name, (_SCOPED.findall(name) or [None])[-1] or scope_of_function(f))
            for f, op, name in ops]


@pytest.fixture(scope="module")
def lowered_texts():
    """``(lowered text with names, optimized HLO, lowered text without
    locations)`` of a program. Compiled with jax's persistent cache off: its
    key leaves op names out, so with it on the null build would LOAD the
    scoped build's executable, names and all (what ``utils/device.py`` keys
    the cache against)."""
    from jax._src import compilation_cache
    cache = {}
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    # jax decides ONCE a process whether the cache is used: a test that ran
    # earlier in this worker with the cache on would keep it on here
    compilation_cache.reset_cache()

    def get(program, null=False):
        if (program, null) not in cache:
            with (sp.null_scopes() if null else contextlib.nullcontext()):
                low = sp.lowered(program)
                cache[(program, null)] = (low.as_text(debug_info=True),
                                          low.compile().as_text(), low.as_text())
        return cache[(program, null)]

    yield get
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("program", sp.PROGRAMS)
def test_every_op_that_costs_device_time_carries_a_declared_scope(program, lowered_texts):
    """Every ``dot_general``, custom call, gather, scatter,
    ``dynamic_update_slice`` and sort of the lowered module has a declared
    scope in its ``op_name``, but the scan's own stack of saved activations
    (``while/body/dynamic_update_slice`` directly under a scan: the reader's
    ``act.stack``)."""
    found = heavy_ops(lowered_texts(program)[0])
    assert len(found) > 10, found
    loose = [(op, name) for op, name, sc in found
             if sc is None and not _ACT_STACK.search(name)]
    assert not loose, loose
    undeclared = {sc for _, _, sc in found if sc is not None and sc not in SCOPES}
    assert not undeclared, undeclared
    if program == "train_step":
        stack = [name for _, name, sc in found if sc is None]
        assert stack and all(_ACT_STACK.search(n) for n in stack)


_EXPECTED = {
    "train_step": {"embed", "attn.qkv", "attn.core", "attn.out", "mlp.up", "mlp.down",
                   "head", "loss"},
    "bloom.decode_chunk": {"embed", "attn.qkv", "attn.core", "attn.out", "kv.append",
                           "mlp.up", "mlp.down", "head", "kv.gather", "kv.copy_back"},
    "bloom.prefill": {"embed", "attn.qkv", "attn.core", "attn.out", "mlp.up", "mlp.down",
                      "head"},
    "hybrid.decode_chunk": {"ssm.in", "ssm.update", "ssm.out", "moe.router", "moe.plan",
                            "moe.rows", "moe.experts", "moe.shared", "attn.core",
                            "kv.append", "head", "kv.gather", "kv.copy_back"},
    "sdar.decode_chunk": {"attn.qkv", "attn.core", "kv.append", "moe.router", "moe.plan",
                          "moe.rows", "moe.experts", "head", "kv.gather", "kv.copy_back"},
    "lfm2.decode_chunk": {"sconv.in", "sconv.conv", "sconv.out", "mlp.up", "mlp.act",
                          "mlp.down", "moe.router", "moe.plan", "moe.rows", "moe.experts",
                          "attn.qkv", "attn.core", "kv.append", "head", "kv.gather",
                          "kv.copy_back"},
}


@pytest.mark.parametrize("program", sp.PROGRAMS)
def test_the_program_holds_the_scopes_its_metrics_read(program, lowered_texts):
    names = re.findall(r'^#loc\d+ = loc\("([^"]*)"', lowered_texts(program)[0], re.M)
    held = {sc for name in names for sc in _SCOPED.findall(name)}
    assert _EXPECTED[program] <= held, _EXPECTED[program] - held
    assert held <= set(SCOPES), held - set(SCOPES)


# -------------------------------------------------- the program is unchanged
def strip_metadata(hlo: str):
    """Optimized HLO text, line by line, without what a scope may move: each
    instruction's ``metadata={...}`` and the module's file-name and
    stack-frame tables; and with every instruction's number replaced by the
    order of its first appearance (two builds of one source number a few
    instructions otherwise: jax names an inner function's ops by a counter
    that outlives a build)."""
    hlo = re.sub(r",? ?metadata=\{[^{}]*\}", "", hlo)
    out, skipping, seen = [], False, {}

    def renumber(m):
        return seen.setdefault(m.group(0), f"%{m.group(1)}#{len(seen)}")

    for line in hlo.splitlines():
        if re.match(r"(FileNames|FunctionNames|FileLocations|StackFrames)\b", line):
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        elif not skipping:
            out.append(re.sub(
                r"%([A-Za-z_][\w\-]*(?:\.[A-Za-z_][\w\-]*)*)(?:\.\d+)?\b", renumber, line))
    return out


@pytest.mark.parametrize("program", sp.PROGRAMS)
def test_a_scope_changes_nothing_but_metadata(program, lowered_texts):
    """The program built with ``observability.scope`` patched to a null
    context is the scoped one: the module jax hands the compiler is the same
    text once locations are dropped, and the optimized HLO is equal
    instruction for instruction once the metadata and the debug tables are
    stripped."""
    _, scoped, scoped_in = lowered_texts(program)
    _, null, null_in = lowered_texts(program, null=True)
    assert SCOPE_PREFIX + "head" in scoped or SCOPE_PREFIX + "loss" in scoped
    assert not _SCOPED.search(" ".join(re.findall(r'op_name="([^"]*)"', null)))
    assert scoped_in == null_in
    a, b = strip_metadata(scoped), strip_metadata(null)
    diff = [(x, y) for x, y in zip(a, b) if x != y]
    assert len(a) == len(b) and not diff, (len(a), len(b), diff[:3])
