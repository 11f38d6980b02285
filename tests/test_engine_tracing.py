"""The engine's own names in a profiler trace (docs/SERVING.md#tracing):
``serve.*`` host spans around each phase of ``Engine.step``, step
programs jitted as ``serve_<name>``, and named model scopes, none of
which may change what runs on the device."""
import contextlib
import glob
import os
import re

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ServeConfig
from repro.models.registry import build_model, get_smoke_config
from repro.serving import engine as engine_mod
from repro.serving.engine import Engine
from repro.serving.request import Request

pytestmark = pytest.mark.slow

PHASES = ("serve.admit", "serve.plan", "serve.inputs", "serve.sample",
          "serve.commit")
SCOPES = ("embed", "attn_in", "kv_write", "attn_kernel", "attn_out", "mlp",
          "norm", "head")


def make_engine(**kw):
    cfg = get_smoke_config("qwen3_0_6b").replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(**{**dict(max_batch=2, max_seq=96, page_size=8,
                                 spec_decode=True, aot_warmup=True), **kw})
    return Engine(m, params, scfg)


def serve_spans(logdir):
    """[name, start_ns, end_ns] of every ``serve.*`` host span."""
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        out.append([e.name, e.start_ns,
                                    e.start_ns + e.duration_ns])
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced_steps(tmp_path_factory):
    """{kind: [(step span, [spans nested in it])]} over a traced run that
    takes mixed, decode and verify steps."""
    eng = make_engine()
    logdir = str(tmp_path_factory.mktemp("trace"))
    # a repeating prompt, so that the n-gram drafter proposes and the
    # engine verifies; a second request so that some steps decode plainly
    loop = [5, 6, 7, 8] * 6
    jax.profiler.start_trace(logdir)
    try:
        eng.submit(Request(prompt=list(range(10, 30)), max_new_tokens=12,
                           eos_id=None))
        eng.submit(Request(prompt=loop, max_new_tokens=12, eos_id=None))
        eng.run()
    finally:
        jax.profiler.stop_trace()
    st = eng.stats()
    assert st["mixed_steps"] and st["decode_batch_steps"] \
        and st["verify_steps"], st
    spans = serve_spans(logdir)
    out = {}
    for step in (s for s in spans if s[0] == "serve.step"):
        inner = [s for s in spans if s is not step
                 and step[1] <= s[1] and s[2] <= step[2]]
        kinds = [s[0].rsplit(".", 1)[1] for s in inner
                 if s[0].startswith("serve.dispatch.")
                 and s[0] != "serve.dispatch.copy"]
        assert len(kinds) <= 1, kinds
        out.setdefault(kinds[0] if kinds else "none", []).append(
            (step, inner))
    return out


@pytest.mark.parametrize("kind", ["decode", "mixed", "verify"])
def test_each_step_kind_nests_its_phases(traced_steps, kind):
    """One ``serve.step`` per tick, with every phase of the tick nested
    inside it and the dispatch of the step's own program."""
    assert traced_steps.get(kind), sorted(traced_steps)
    for step, inner in traced_steps[kind]:
        names = {s[0] for s in inner}
        assert set(PHASES) | {f"serve.dispatch.{kind}"} <= names, names
        assert "serve.step" not in names
        # phases do not overlap one another (one thread, in order)
        top = sorted((s for s in inner if s[0] in PHASES),
                     key=lambda s: s[1])
        assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))
    if kind == "mixed":
        # the first tick admits: its prefix-cache lookups sit in admit
        step, inner = traced_steps["mixed"][0]
        admit = next(s for s in inner if s[0] == "serve.admit")
        looks = [s for s in inner if s[0] == "serve.prefix_lookup"]
        assert looks and all(admit[1] <= s[1] and s[2] <= admit[2]
                             for s in looks)


@pytest.mark.parametrize("kind", ["decode", "mixed", "verify"])
def test_phases_cover_the_step(traced_steps, kind):
    """The phase spans cover at least 90 % of the ``serve.step`` time."""
    step_ns = covered_ns = 0
    for step, inner in traced_steps[kind]:
        step_ns += step[2] - step[1]
        edge = step[1]
        for s in sorted(inner, key=lambda s: s[1]):
            a, b = max(s[1], edge), s[2]
            if b > a:
                covered_ns += b - a
                edge = b
    assert covered_ns >= 0.9 * step_ns, covered_ns / step_ns


@pytest.fixture(scope="module")
def engines():
    """The engine as it is, and as the parent built it: step programs
    jitted from lambdas and no model scopes."""
    named = make_engine()

    @contextlib.contextmanager
    def no_scope(name):
        yield
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", no_scope)
        mp.setattr(engine_mod, "_named", lambda fn, name: fn)
        plain = make_engine()
    return named, plain


def test_programs_are_named_serve(engines):
    named, plain = engines
    mods = {n: next(iter(f._exe.values())).as_text().split()[1].rstrip(",")
            for n, f in named._step_fns().items()}
    assert mods == {n: f"jit_serve_{n}" for n in
                    ("decode", "mixed", "copy", "verify")}
    assert {e.as_text().split()[1].rstrip(",")
            for e in named.compiled_steps()} == set(mods.values())
    assert {e.as_text().split()[1].rstrip(",")
            for e in plain.compiled_steps()} == {"jit__lambda",
                                                "jit__copy_pages_fn"}
    assert "allocated_kv_bytes" not in named.stats()


def _strip(hlo: str) -> str:
    """HLO text without metadata, the module's name and the source
    location tables."""
    out = []
    for line in hlo.splitlines():
        if re.match(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$",
                    line) or re.match(r"^\d+ ", line):
            continue
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        out.append(re.sub(r"^HloModule \S+,", "HloModule _,", line))
    return "\n".join(out)


@pytest.mark.parametrize("fn", ["decode", "mixed", "copy", "verify"])
def test_scopes_and_names_change_only_metadata(engines, fn):
    """Each step program compiles to what the unnamed, unscoped program
    compiles to, but for metadata and the module's name; and the model
    scopes are in its ``op_name`` metadata."""
    named, plain = engines
    a = next(iter(named._step_fns()[fn]._exe.values())).as_text()
    b = next(iter(plain._step_fns()[fn]._exe.values())).as_text()
    assert _strip(a) == _strip(b)
    assert a != b
    if fn != "copy":
        found = set(re.findall(r'op_name="[^"]*/(' + "|".join(SCOPES)
                               + r')/', a))
        assert found == set(SCOPES), found
