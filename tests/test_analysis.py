"""Static-analysis tier: the analyzer is itself tested by injection.

Every load-bearing claim of `python -m repro check` gets a test that
*injects* the violation it is supposed to catch (the ISSUE 8 acceptance
criteria):

- a second host-transfer surface in the decode step → trace.one-transfer;
- an f32 dequant materialized before ``dot_general`` → trace.int8dot
  (driven through the real ``quant_matmul variant="dequant"`` baseline
  body, so the detector is proven against production kernel code);
- a dropped ``plan=`` at a forward site → QFT002;
- a hardcoded ``interpret=True`` → QFT004;

plus per-rule lint coverage with ``# qft: noqa`` suppression, CLI exit
codes, and the report JSON's schema and summary.
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jaxpr_checks import (callback_count,
                                         dequant_dot_violations,
                                         integer_dot_count,
                                         transfer_surfaces)
from repro.analysis.lint import lint_source
from repro.core import permissive
from repro.kernels.quant_matmul import quant_matmul
from repro.models import ModelConfig
from repro.pipeline.cli import main as cli_main
from repro.serve.deploy import abstract_deploy_surfaces
from repro.serve.engine import ServeConfig, serve_trace_surfaces

TINY = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                   n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                   head_dim=8, scan_layers=False, remat=False)


def _decode_surfaces():
    plan, _ex, deployed = abstract_deploy_surfaces(TINY, permissive())
    scfg = ServeConfig(max_slots=2, max_len=32, prefill_chunk=8)
    s = serve_trace_surfaces(TINY, plan=plan, scfg=scfg)
    return s, deployed


# ---------------------------------------------------------------------------
# Layer 1 injection: one-transfer
# ---------------------------------------------------------------------------

def test_clean_decode_step_has_one_transfer_surface():
    s, deployed = _decode_surfaces()
    closed = jax.make_jaxpr(s["decode_fn"])(deployed, s["cache"], s["state"])
    assert callback_count(closed) == 0
    assert transfer_surfaces(closed) == 1


def test_injected_second_host_transfer_is_caught():
    """A pure_callback smuggled anywhere into the decode graph — even
    nested under other ops — must bump the surface count past 1."""
    s, deployed = _decode_surfaces()

    def leaky_decode(params, cache, state):
        cache, state, cur, emit = s["decode_fn"](params, cache, state)
        # the injected violation: a host round-trip on the emitted token
        cur = jax.pure_callback(
            lambda t: t, jax.ShapeDtypeStruct(cur.shape, cur.dtype), cur)
        return cache, state, cur, emit

    closed = jax.make_jaxpr(leaky_decode)(deployed, s["cache"], s["state"])
    assert callback_count(closed) == 1
    assert transfer_surfaces(closed) == 2


def test_decode_step_contains_device_rng():
    """Non-vacuity for the sampling tentpole: the decode trace must carry
    the device-side PRNG (random_split for the per-slot key chain,
    random_bits for the categorical) — if sampling ever silently degraded
    to a trace-time host draw, these ops would vanish from the jaxpr."""
    s, deployed = _decode_surfaces()
    closed = jax.make_jaxpr(s["decode_fn"])(deployed, s["cache"], s["state"])
    text = str(closed)
    assert "random_split" in text and "random_bits" in text


def _hlo_computations(text: str) -> tuple[str, dict[str, list[str]]]:
    """Compiled HLO text as (entry name, {computation: instruction lines})."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(", line)
        if head:
            name = head.group(2)
            comps[name] = []
            if head.group(1):
                entry = name
        elif name is not None and line.strip() == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return entry, comps


def test_greedy_decode_step_sorts_only_inside_a_branch():
    """Every ``sort`` of the compiled decode step (top-k's and top-p's)
    lies in a conditional's branch: a step in which no live slot samples
    takes the other branch and sorts nothing.  Walked from the entry
    through every call but a conditional's branches."""
    s, deployed = _decode_surfaces()
    text = jax.jit(s["decode_fn"]).lower(
        deployed, s["cache"], s["state"]).compile().as_text()
    entry, comps = _hlo_computations(text)
    callee = re.compile(r"(?:to_apply|calls|body|condition)=%([\w.\-]+)")

    def has_sort(lines):
        return any(re.search(r"\ssort\(", ln) for ln in lines)

    assert sum(has_sort([ln]) for c in comps.values() for ln in c) >= 2
    seen, stack = set(), [entry]
    while stack:
        name = stack.pop()
        if name in seen:
            continue
        seen.add(name)
        assert not has_sort(comps[name]), name
        for ln in comps[name]:
            stack.extend(callee.findall(ln))


def test_injected_host_rng_draw_is_caught():
    """The smuggling vector the lint rule (QFT003, source level) and this
    structural gate close together: a host np.random draw pushed into the
    decode step via pure_callback.  The callback IS a second transfer
    surface — trace.one-transfer fails before the nondeterminism could
    ship."""
    s, deployed = _decode_surfaces()

    def leaky_decode(params, cache, state):
        cache, state, cur, emit = s["decode_fn"](params, cache, state)
        # the injected violation: "resample" the token on the host
        cur = jax.pure_callback(
            lambda t: np.random.randint(  # qft: noqa[QFT003]
                0, 64, t.shape).astype(t.dtype),
            jax.ShapeDtypeStruct(cur.shape, cur.dtype), cur)
        return cache, state, cur, emit

    closed = jax.make_jaxpr(leaky_decode)(deployed, s["cache"], s["state"])
    assert callback_count(closed) == 1
    assert transfer_surfaces(closed) == 2


# ---------------------------------------------------------------------------
# Layer 1 injection: int8dot / f32-dequant materialization
# ---------------------------------------------------------------------------

def _qmm_avals(m=128, k=128, n=128):
    x = jax.ShapeDtypeStruct((m, k), jnp.float32)
    q = jax.ShapeDtypeStruct((k // 2, n), jnp.uint8)
    s_wl = jax.ShapeDtypeStruct((k,), jnp.float32)
    s_wr = jax.ShapeDtypeStruct((n,), jnp.float32)
    return x, q, s_wl, s_wr


def test_int8dot_kernel_body_is_clean():
    closed = jax.make_jaxpr(
        lambda x, q, a, b: quant_matmul(x, q, a, b, interpret=None,
                                        variant="int8dot"))(*_qmm_avals())
    assert dequant_dot_violations(closed) == []
    # non-vacuity: the integer weights really are a dot operand
    assert integer_dot_count(closed) >= 1


def test_injected_f32_dequant_before_dot_is_caught():
    """The dequant baseline variant materializes f32 weights before the
    dot — exactly the violation signature the analyzer must flag (it is
    kept in-tree as the kernel bench's baseline body, which makes it the
    perfect injection vehicle)."""
    closed = jax.make_jaxpr(
        lambda x, q, a, b: quant_matmul(x, q, a, b, interpret=None,
                                        variant="dequant"))(*_qmm_avals())
    bad = dequant_dot_violations(closed)
    assert bad, "dequant variant must trip the int8dot invariant"
    assert "convert_element_type" in bad[0]


def test_handwritten_dequant_matmul_is_caught():
    """The detector is structural, not kernel-specific: a plain XLA
    dequantize-then-dot is flagged too."""
    def f(x, q, s):
        w = q.astype(jnp.float32) * s          # materialized f32 [K, N]
        return x @ w

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 32), jnp.int8),
        jax.ShapeDtypeStruct((32,), jnp.float32))
    assert dequant_dot_violations(closed)


def test_float_weights_do_not_false_positive():
    def f(x, w):
        return x @ (w.astype(jnp.float32) * 2.0)   # bf16→f32: fine

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 32), jnp.bfloat16))
    assert dequant_dot_violations(closed) == []


def test_int4_unpack_does_not_false_positive():
    """uint8→int8 nibble unpack is int→int and must not trip the rule
    when the integer result is the dot operand."""
    def f(x, q4, s_wr):
        lo = (q4 & 0xF).astype(jnp.int8) - 8
        y = jax.lax.dot_general(x.astype(jnp.int8), lo,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return y * s_wr

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((8, 16), jnp.int8),
        jax.ShapeDtypeStruct((16, 32), jnp.uint8),
        jax.ShapeDtypeStruct((32,), jnp.float32))
    assert dequant_dot_violations(closed) == []
    assert integer_dot_count(closed) == 1


# ---------------------------------------------------------------------------
# Layer 2: per-rule lint coverage
# ---------------------------------------------------------------------------

def _ids(diags):
    return [d.check for d in diags]


def test_qft001_unnamed_qlinear():
    src = "p = init_qlinear(k, 4, 8, cfg)\n"
    diags = lint_source(src, "src/repro/models/foo.py")
    assert _ids(diags) == ["QFT001"]
    assert diags[0].line == 1
    clean = "p = init_qlinear(k, 4, 8, cfg, name='layers.mlp.up')\n"
    assert lint_source(clean, "src/repro/models/foo.py") == []


def test_qft002_dropped_plan_is_caught():
    """Acceptance: a dropped plan= at a qlinear forward site yields a
    file:line-qualified diagnostic."""
    src = "out = forward(params, cfg, qcfg, batch)\n"
    diags = lint_source(src, "src/repro/serve/foo.py")
    assert _ids(diags) == ["QFT002"]
    assert diags[0].file == "src/repro/serve/foo.py"
    assert diags[0].line == 1
    # teacher forward (qcfg literal None) is exempt
    assert lint_source("out = forward(params, cfg, None, batch)\n",
                       "src/repro/serve/foo.py") == []
    # threading the plan satisfies the rule
    assert lint_source("out = forward(params, cfg, qcfg, batch, plan=p)\n",
                       "src/repro/serve/foo.py") == []
    # tests are fixture territory: rule scoped out there
    assert lint_source(src, "tests/test_foo.py") == []


def test_qft003_host_sync_in_traced_step():
    src = ("def make_thing(cfg):\n"
           "    def thing_step(params, state):\n"
           "        jax.device_get(state)\n"
           "        return state\n"
           "    return thing_step\n")
    diags = lint_source(src, "src/repro/serve/foo.py")
    assert _ids(diags) == ["QFT003"]
    # rule is scoped to serve/train: same code elsewhere is not flagged
    assert lint_source(src, "src/repro/kernels/foo.py") == []


def test_qft003_host_rng_in_traced_step():
    """np.random inside a ``*_step`` body: the draw happens once at trace
    time and bakes a constant into the compiled step — flagged at the
    source level (the structural twin is
    test_injected_host_rng_draw_is_caught)."""
    src = ("def make_thing(cfg):\n"
           "    def thing_step(params, state):\n"
           "        noise = np.random.normal(size=state.shape)\n"
           "        return state + noise\n"
           "    return thing_step\n")
    diags = lint_source(src, "src/repro/train/foo.py")
    assert _ids(diags) == ["QFT003"]
    assert "trace-time constant" in diags[0].message
    # suppressible, like every qft rule
    assert lint_source(src.replace(
        "state.shape)", "state.shape)  # qft: noqa[QFT003]"),
        "src/repro/train/foo.py") == []
    # jax.random draws (keyed, device-side) are the sanctioned path
    keyed = ("def make_thing(cfg):\n"
             "    def thing_step(params, state, key):\n"
             "        return state + jax.random.normal(key, state.shape)\n"
             "    return thing_step\n")
    assert lint_source(keyed, "src/repro/train/foo.py") == []


def test_qft003_engine_host_loop():
    src = ("class Engine:\n"
           "    def step(self):\n"
           "        a = jax.device_get(self.state)\n"
           "        b = jax.device_get(self.more)\n"
           "        return a, b\n")
    diags = lint_source(src, "src/repro/serve/engine2.py")
    assert _ids(diags) == ["QFT003", "QFT003"]


def test_qft004_hardcoded_interpret_is_caught():
    """Acceptance: a hardcoded interpret=True yields a file:line
    diagnostic; interpret=None and interpret=var pass."""
    diags = lint_source("y = quant_matmul(x, q, s, interpret=True)\n",
                        "src/repro/kernels/foo.py")
    assert _ids(diags) == ["QFT004"]
    assert diags[0].line == 1
    assert lint_source("y = quant_matmul(x, q, s, interpret=None)\n",
                       "src/repro/kernels/foo.py") == []
    assert lint_source("y = quant_matmul(x, q, s, interpret=interp)\n",
                       "src/repro/kernels/foo.py") == []
    # def-site default interpret=False is the same violation
    assert _ids(lint_source("def f(x, interpret=False):\n    return x\n",
                            "src/repro/kernels/foo.py")) == ["QFT004"]


def test_qft005_wall_clock_and_unseeded_random():
    src = ("t0 = time.perf_counter()\n"
           "x = np.random.rand(4)\n"
           "k = jax.random.normal(key, (4,))\n"     # keyed: exempt
           "r = np.random.RandomState(0).rand(4)\n")  # seeded: exempt
    diags = lint_source(src, "paper/foo.py")
    assert _ids(diags) == ["QFT005", "QFT005"]
    assert [d.line for d in diags] == [1, 2]
    # outside paper/ the rule does not apply
    assert lint_source(src, "src/repro/train/foo.py") == []


def test_qft006_mutable_dataclass_default():
    src = ("@dataclasses.dataclass\n"
           "class Cfg:\n"
           "    xs: list = []\n"
           "    ok: tuple = ()\n"
           "    also_ok: list = dataclasses.field(default_factory=list)\n")
    diags = lint_source(src, "src/repro/models/config2.py")
    assert _ids(diags) == ["QFT006"]


def test_noqa_suppression_is_rule_scoped():
    flagged = "y = f(x, interpret=True)\n"
    scoped = "y = f(x, interpret=True)  # qft: noqa[QFT004]\n"
    wrong = "y = f(x, interpret=True)  # qft: noqa[QFT005]\n"
    bare = "y = f(x, interpret=True)  # qft: noqa\n"
    p = "src/repro/kernels/foo.py"
    assert _ids(lint_source(flagged, p)) == ["QFT004"]
    assert lint_source(scoped, p) == []
    assert _ids(lint_source(wrong, p)) == ["QFT004"]
    assert lint_source(bare, p) == []


# ---------------------------------------------------------------------------
# CLI exit codes + report round trip
# ---------------------------------------------------------------------------

def test_check_cli_clean_tree_exits_zero(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    rc = cli_main(["check", "--lint-only", "--paths", str(clean)])
    capsys.readouterr()
    assert rc == 0


def test_check_cli_injected_violation_exits_nonzero(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("y = quant_matmul(x, q, s, interpret=True)\n")
    rc = cli_main(["check", "--lint-only", "--paths", str(bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "QFT004" in out and "bad.py" in out


def test_check_cli_json_report_validates(tmp_path, capsys):
    """The JSON report's summary counts its body's diagnostics by severity,
    on a clean tree and on one with a planted error."""
    bad = tmp_path / "bad.py"
    bad.write_text("y = quant_matmul(x, q, s, interpret=True)\n")
    report_path = tmp_path / "ANALYSIS_report.json"
    for paths, want_errors in ((["src/repro/analysis"], 0), ([str(bad)], 1)):
        rc = cli_main(["check", "--lint-only", "--paths", *paths,
                       "--json", str(report_path)])
        capsys.readouterr()
        assert rc == want_errors
        rep = json.loads(report_path.read_text())
        assert rep["schema"] == 1 and rep["tool"] == "repro-check"
        for key, sev in (("errors", "error"), ("warnings", "warning"),
                         ("infos", "info"), ("skips", "skip")):
            assert rep["summary"][key] == sum(
                d["severity"] == sev for d in rep["diagnostics"]), key
        assert rep["summary"]["errors"] == want_errors


def test_check_cli_unknown_config_is_usage_error(capsys):
    rc = cli_main(["check", "--config", "not-a-config", "--trace-only"])
    capsys.readouterr()
    assert rc == 2
