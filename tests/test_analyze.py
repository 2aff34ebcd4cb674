"""Tests for the ``tools.analyze`` static-analysis suite.

Each rule gets fixture snippets that must trip it and clean snippets
that must not; the waiver machinery gets a honored-waiver case; the
parity rule gets a mutation test (copy the real backend sources, bend a
C ``#define``, assert detection). The capstone asserts the shipped tree
itself analyzes clean — the CI ``static-analysis`` job's contract.
"""

import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.analyze import (  # noqa: E402
    RULES,
    WAIVERS_PATH,
    apply_waivers,
    load_waivers,
    run_rules,
)
from tools.analyze import (  # noqa: E402
    cbounds,
    determinism,
    forksafety,
    jaxpurity,
    parity,
    schema,
)
from tools.analyze.findings import Finding, Waiver  # noqa: E402

CORE = "src/repro/core"


def _tree(tmp_path, files):
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return tmp_path


def _codes(findings):
    return {f.code for f in findings}


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------
def test_determinism_trips_on_each_violation(tmp_path):
    root = _tree(
        tmp_path,
        {
            "src/repro/core/bad.py": (
                "import random\n"
                "import time\n"
                "import numpy as np\n"
                "from concurrent.futures import as_completed\n"
                "def f(pool, futs):\n"
                "    random.random()\n"
                "    time.time()\n"
                "    np.random.rand(3)\n"
                "    np.random.default_rng()\n"
                "    np.random.RandomState(0)\n"
                "    list(pool.imap_unordered(abs, [1]))\n"
                "    list(as_completed(futs))\n"
                "    return np.array({1, 2, 3})\n"
            ),
        },
    )
    codes = _codes(determinism.run(root))
    assert codes == {
        "stdlib-random",
        "wall-clock",
        "np-random-module",
        "unseeded-default-rng",
        "np-random-state",
        "set-order-array",
        "unordered-completion",
    }


def test_determinism_clean_snippets(tmp_path):
    root = _tree(
        tmp_path,
        {
            "src/repro/core/good.py": (
                "import time\n"
                "import numpy as np\n"
                "def f(seed):\n"
                "    ss = np.random.SeedSequence(seed)\n"
                "    rng = np.random.default_rng(ss.spawn(1)[0])\n"
                "    t0 = time.perf_counter()\n"
                "    a = np.array(sorted({3, 1, 2}))\n"
                "    return rng.integers(10), a, time.perf_counter() - t0\n"
            ),
            # set-order feeding arrays is fine OUTSIDE engine paths
            "src/repro/training/loose.py": (
                "import numpy as np\n"
                "def g(xs):\n"
                "    return np.array(list(set(xs)))\n"
            ),
            # a local named like a stdlib module is not the module
            "src/repro/core/shadow.py": (
                "def h(random, time):\n"
                "    return random.random() + time.time()\n"
            ),
            # ordered pool iteration preserves submission order
            "src/repro/core/pooluse.py": (
                "def k(pool, xs):\n"
                "    return list(pool.imap(abs, xs))\n"
            ),
        },
    )
    assert determinism.run(root) == []


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------
@pytest.fixture()
def backend_copy(tmp_path):
    dest = tmp_path / CORE
    dest.mkdir(parents=True)
    for name in ("fastsim.py", "_fastsim_c.c", "fastsim_c.py",
                 "fastsim_jax.py"):
        shutil.copy(REPO / CORE / name, dest / name)
    return tmp_path


def test_parity_clean_on_real_backends(backend_copy):
    assert parity.run(backend_copy) == []


def test_parity_detects_mutated_define(backend_copy):
    c = backend_copy / CORE / "_fastsim_c.c"
    src = c.read_text()
    assert "#define NIL (-1)" in src
    c.write_text(src.replace("#define NIL (-1)", "#define NIL (-2)"))
    findings = parity.run(backend_copy)
    assert "nil-sentinel" in _codes(findings)


def test_parity_detects_enum_drift(backend_copy):
    py = backend_copy / CORE / "fastsim_c.py"
    src = py.read_text()
    assert "SC_COUNT = 14" in src
    py.write_text(src.replace("SC_COUNT = 14", "SC_COUNT = 15"))
    findings = parity.run(backend_copy)
    assert "sc-enum" in _codes(findings)


def test_parity_detects_hist_mismatch(backend_copy):
    py = backend_copy / CORE / "fastsim_c.py"
    src = py.read_text()
    assert "HIST_LEN = 1024" in src
    py.write_text(src.replace("HIST_LEN = 1024", "HIST_LEN = 512"))
    assert "hist-buckets" in _codes(parity.run(backend_copy))


def test_parity_detects_dtype_drift(backend_copy):
    c = backend_copy / CORE / "_fastsim_c.c"
    src = c.read_text()
    mutated = src.replace("const int32_t *P", "const int64_t *P")
    assert mutated != src
    c.write_text(mutated)
    assert "c-signature" in _codes(parity.run(backend_copy))


# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------
SCHEMA_BAD = '''
from dataclasses import dataclass

@dataclass(frozen=True)
class Spec:
    alpha: float
    beta: float
    gamma: int = 3

    def to_dict(self):
        return {"alpha": self.alpha, "beta": self.beta}

    @staticmethod
    def from_dict(d):
        return Spec(alpha=d["alpha"], beta=d["beta"])
'''

SCHEMA_GOOD = '''
from dataclasses import asdict, dataclass

@dataclass(frozen=True)
class Spec:
    alpha: float
    beta: float

    def to_dict(self):
        return asdict(self)

    @staticmethod
    def from_dict(d):
        return Spec(**d)
'''


def test_schema_trips_on_dropped_field(tmp_path):
    root = _tree(tmp_path, {"src/repro/scenario/spec.py": SCHEMA_BAD})
    findings = schema.run(root)
    codes = _codes(findings)
    assert "field-not-serialized" in codes
    assert "field-not-deserialized" in codes
    assert all("gamma" in f.message for f in findings)


def test_schema_clean_on_asdict_splat(tmp_path):
    root = _tree(tmp_path, {"src/repro/scenario/spec.py": SCHEMA_GOOD})
    assert schema.run(root) == []


def test_schema_flags_missing_serializer(tmp_path):
    root = _tree(
        tmp_path,
        {
            "src/repro/scenario/spec.py": (
                "from dataclasses import dataclass\n"
                "@dataclass\n"
                "class Runtime:\n"
                "    x: int\n"
            )
        },
    )
    assert _codes(schema.run(root)) == {"missing-serializer"}


def test_schema_clean_on_shipped_tree():
    findings = apply_waivers(schema.run(REPO), load_waivers(WAIVERS_PATH))
    unwaived = [f for f in findings if not f.waived]
    assert unwaived == []


# ---------------------------------------------------------------------------
# jaxpurity
# ---------------------------------------------------------------------------
JAX_BAD = '''
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def f(x):
    y = x + 1
    if y > 0:
        y = y * 2
    z = float(y)
    w = y.item()
    v = np.log(y)
    return jnp.where(y > 0, y, 0), z, w, v
'''

JAX_GOOD = '''
import functools
import jax
import jax.numpy as jnp
import numpy as np

@functools.partial(jax.jit, static_argnames=("mode",))
def f(x, *, flag, mode=None):
    if mode is None:
        mode = "fast"
    if flag:
        x = x * 2
    n = x.shape[0]
    if n > 4:
        x = x[:4]
    scale = np.float64(2.0)
    return jnp.where(x > 0, x * scale, 0.0)

def host_side(result):
    # not a traced scope: concretization is fine here
    return float(np.asarray(result).sum())
'''


def test_jaxpurity_trips_on_each_leak(tmp_path):
    root = _tree(tmp_path, {"src/repro/kernels/bad.py": JAX_BAD})
    codes = _codes(jaxpurity.run(root))
    assert codes == {
        "tracer-branch",
        "python-coercion",
        "item-call",
        "numpy-on-tracer",
    }


def test_jaxpurity_statics_and_host_code_clean(tmp_path):
    root = _tree(tmp_path, {"src/repro/kernels/good.py": JAX_GOOD})
    assert jaxpurity.run(root) == []


def test_jaxpurity_partial_indirection(tmp_path):
    # the repo idiom: f = functools.partial(impl, **statics); jax.jit(f)
    root = _tree(
        tmp_path,
        {
            "src/repro/kernels/indirect.py": (
                "import functools\n"
                "import jax\n"
                "def _impl(x, *, k):\n"
                "    return x.item()\n"
                "def build(k):\n"
                "    f = functools.partial(_impl, k=k)\n"
                "    return jax.jit(f)\n"
            )
        },
    )
    assert _codes(jaxpurity.run(root)) == {"item-call"}


# ---------------------------------------------------------------------------
# waivers
# ---------------------------------------------------------------------------
def test_waiver_honored(tmp_path):
    f = Finding("determinism", "wall-clock", "src/x.py", 3, "time.time()")
    g = Finding("determinism", "wall-clock", "src/y.py", 9, "time.time()")
    w = Waiver(
        rule="determinism", path="src/x.py", reason="telemetry", code="wall-clock"
    )
    apply_waivers([f, g], [w])
    assert f.waived and f.waiver_reason == "telemetry"
    assert not g.waived
    assert w.used == 1


def test_waiver_contains_narrowing():
    f = Finding("schema", "missing-from", "src/x.py", 1, "dataclass A ...")
    w = Waiver(rule="schema", path="src/x.py", reason="r", contains="dataclass B")
    assert not w.matches(f)


def test_load_waivers_reads_every_shipped_entry():
    import tomllib

    entries = tomllib.loads(WAIVERS_PATH.read_text())["waiver"]
    got = load_waivers(WAIVERS_PATH)
    assert [
        {k: v for k, v in vars(w).items() if k != "used" and v is not None}
        for w in got
    ] == entries


def test_waiver_requires_reason(tmp_path):
    bad = tmp_path / "w.toml"
    bad.write_text('[[waiver]]\nrule = "schema"\npath = "x.py"\n')
    with pytest.raises(ValueError):
        load_waivers(bad)


# ---------------------------------------------------------------------------
# driver / shipped tree
# ---------------------------------------------------------------------------
def test_rule_registry_complete():
    assert set(RULES) == {
        "determinism", "parity", "schema", "jaxpurity", "docs",
        "forksafety", "cbounds",
    }


def test_every_rule_declares_codes():
    for name, mod in RULES.items():
        codes = getattr(mod, "CODES", None)
        assert isinstance(codes, dict) and codes, name
        assert all(
            isinstance(c, str) and isinstance(d, str)
            for c, d in codes.items()
        ), name


def test_unknown_rule_rejected():
    with pytest.raises(KeyError):
        run_rules(REPO, ["nope"])


def test_shipped_tree_is_clean():
    """The CI static-analysis contract: all rules, waivers applied,
    nothing unwaived, no stale waivers."""
    waivers = load_waivers(WAIVERS_PATH)
    findings = run_rules(REPO, None, waivers)
    unwaived = [f.render() for f in findings if not f.waived]
    assert unwaived == []
    stale = [w.reason for w in waivers if w.used == 0]
    assert stale == []


def test_cli_exit_codes(tmp_path):
    env_root = str(REPO)
    clean = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--rule", "parity"],
        cwd=env_root,
        capture_output=True,
        text=True,
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr
    bad = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--rule", "nope"],
        cwd=env_root,
        capture_output=True,
        text=True,
    )
    assert bad.returncode == 2


def test_cli_nonzero_on_violation(tmp_path):
    _tree(
        tmp_path,
        {
            "src/repro/core/bad.py": (
                "import numpy as np\n"
                "def f():\n"
                "    return np.random.rand()\n"
            ),
            "tools/__init__.py": "",
        },
    )
    run = subprocess.run(
        [
            sys.executable,
            "-m",
            "tools.analyze",
            "--rule",
            "determinism",
            "--root",
            str(tmp_path),
            "--json",
        ],
        cwd=str(REPO),
        capture_output=True,
        text=True,
    )
    assert run.returncode == 1
    assert "np-random-module" in run.stdout


# ---------------------------------------------------------------------------
# sanitizer wiring (unit level; the full ASan run is the CI c-sanitize job)
# ---------------------------------------------------------------------------
def test_sanitizer_env_parsing(monkeypatch):
    from repro.core import fastsim_c

    monkeypatch.delenv("REPRO_C_SANITIZE", raising=False)
    assert fastsim_c._sanitizers() == ()
    monkeypatch.setenv("REPRO_C_SANITIZE", "undefined,address")
    assert fastsim_c._sanitizers() == ("address", "undefined")
    monkeypatch.setenv("REPRO_C_SANITIZE", "bogus")
    with pytest.raises(ValueError):
        fastsim_c._sanitizers()


def test_sanitizer_cflags_and_name():
    from repro.core import fastsim_c

    assert fastsim_c._san_cflags(()) == []
    flags = fastsim_c._san_cflags(("address", "undefined"))
    assert "-fsanitize=address,undefined" in flags
    assert "-fno-sanitize-recover=undefined" in flags
    assert fastsim_c._so_name("abc", ()) == "fastsim_abc.so"
    assert (
        fastsim_c._so_name("abc", ("address", "undefined"))
        == "fastsim_abc_address_undefined.so"
    )


# ---------------------------------------------------------------------------
# ir: shared flow-analysis infrastructure
# ---------------------------------------------------------------------------
IR_MODULE = '''
import multiprocessing as mp
import numpy as np
from numpy.random import default_rng as rng_ctor


class Bank:
    def __init__(self, plan):
        self.plan = plan

    def feed(self):
        helper(self.plan)


def helper(p):
    return p


def _worker_main(plan):
    bank = Bank(plan)
    bank.feed()


def launch(plan):
    return mp.Process(target=_worker_main, args=(plan,))


def standalone():
    return 3
'''


def _ir():
    import ast

    from tools.analyze.ir import ModuleIR

    return ModuleIR(ast.parse(IR_MODULE))


def test_ir_alias_resolution():
    from tools.analyze.ir import resolve

    ir = _ir()
    import ast

    np_call = ast.parse("np.random.rand()").body[0].value
    assert resolve(ir.aliases.map, np_call.func) == ("numpy.random.rand", True)
    from_call = ast.parse("rng_ctor()").body[0].value
    assert resolve(ir.aliases.map, from_call.func) == (
        "numpy.random.default_rng",
        True,
    )
    local = ast.parse("time.time()").body[0].value
    # `time` was never imported here: not known
    assert resolve(ir.aliases.map, local.func) == ("time.time", False)


def test_ir_call_graph_and_reachability():
    ir = _ir()
    assert ir.process_targets() == {"_worker_main"}
    cone = ir.reachable(["_worker_main"])
    # the worker cone crosses the constructor-typed local: bank.feed()
    assert cone == {"_worker_main", "Bank.__init__", "Bank.feed", "helper"}
    assert "standalone" not in cone
    assert "launch" not in cone


def test_ir_taint_propagation():
    import ast

    from tools.analyze.ir import TaintWalker

    src = (
        "def f(plan):\n"
        "    a = plan.sel\n"          # attr read: tainted
        "    b = a[3]\n"              # subscript view: tainted
        "    c = b + 1\n"             # arithmetic: tainted
        "    d = transform(c)\n"      # call launders
        "    e = {m: a[m] for m in sorted(a)}\n"  # sorted rebuild: clean
        "    g = [x for x in a]\n"    # unsorted comprehension: tainted
        "    a = 0\n"                 # rebind kills taint
        "    h = a\n"
    )
    fn = ast.parse(src).body[0]
    w = TaintWalker({"plan"})
    for stmt in fn.body:
        w.visit(stmt)
    assert {"b", "c", "g"} <= w.tainted
    assert "d" not in w.tainted
    assert "e" not in w.tainted
    assert "a" not in w.tainted
    assert "h" not in w.tainted


# ---------------------------------------------------------------------------
# forksafety
# ---------------------------------------------------------------------------
FORK_BAD = '''
import multiprocessing as mp
import threading


class _Plan:
    """Inputs shipped to workers.

    fork-shared: read-only — workers must never write through this.
    """

    def __init__(self, sel, lengths):
        self.sel = sel
        self.lengths = lengths


def _worker_main(conn, plan: _Plan):
    for m in plan.sel:
        idxs = plan.sel[m]
        idxs += 1
        plan.sel[m] = idxs
    plan.lengths.sort()
    conn.send(None)


def launch(sel, lengths):
    fh = open("trace.bin", "rb")
    plan = _Plan(sel, fh)
    p = mp.Process(
        target=_worker_main, args=(None, plan, threading.Lock())
    )
    return p, lengths


def merge(conns):
    outs = {}
    for c in conns:
        r = c.recv()
        for m in r:
            outs[m] = r[m]
    return outs
'''

FORK_GOOD = '''
import multiprocessing as mp


class _Plan:
    """Inputs shipped to workers.

    fork-shared: read-only — workers must never write through this.
    """

    def __init__(self, sel, lengths):
        self.sel = sel
        self.lengths = lengths


def _worker_main(conn, plan: _Plan):
    total = 0
    for m in sorted(plan.sel):
        local = plan.sel[m].copy()
        local += 1
        total += int(local.sum())
    conn.send(total)


def launch(plan: _Plan):
    return mp.Process(target=_worker_main, args=(None, plan))


def merge(conns):
    outs = {}
    for c in conns:
        r = c.recv()
        for m in sorted(r):
            outs[m] = r[m]
    canon = {m: outs[m] for m in sorted(outs)}
    return canon
'''

CLUSTER_REL = "src/repro/core/cluster.py"


def test_forksafety_trips_on_each_code(tmp_path):
    root = _tree(tmp_path, {CLUSTER_REL: FORK_BAD})
    findings = forksafety.run(root)
    assert _codes(findings) == {
        "worker-plan-mutation",
        "worker-inplace-numpy",
        "unordered-merge",
        "fork-hostile-capture",
    }
    # both the Plan(...) ctor and the Process(...) capture are caught
    hostile = [f for f in findings if f.code == "fork-hostile-capture"]
    assert len(hostile) == 2
    # += on the view and .sort() on the shared array are separate hits
    inplace = [f for f in findings if f.code == "worker-inplace-numpy"]
    assert len(inplace) == 2


def test_forksafety_clean_on_readonly_worker(tmp_path):
    root = _tree(tmp_path, {CLUSTER_REL: FORK_GOOD})
    assert forksafety.run(root) == []


def test_forksafety_reports_syntax_error(tmp_path):
    root = _tree(tmp_path, {CLUSTER_REL: "def broken(:\n"})
    assert _codes(forksafety.run(root)) == {"syntax-error"}


def test_forksafety_clean_on_shipped_tree():
    assert forksafety.run(REPO) == []


def test_forksafety_mutation_worker_plan_write(tmp_path):
    """ISSUE mutation: insert a worker-side ``plan`` mutation into a
    fixture copy of the real cluster.py — exactly one finding."""
    src = (REPO / CLUSTER_REL).read_text()
    anchor = "            idxs = sm[lo:hi]"
    assert anchor in src
    mutated = src.replace(
        anchor, anchor + "\n            plan.sel[m] = idxs"
    )
    root = _tree(tmp_path, {CLUSTER_REL: mutated})
    findings = forksafety.run(root)
    assert [f.code for f in findings] == ["worker-plan-mutation"]
    want = mutated.splitlines().index(
        "            plan.sel[m] = idxs") + 1
    assert findings[0].line == want


def test_forksafety_mutation_unordered_merge(tmp_path):
    """ISSUE mutation: drop the ``sorted(...)`` canonicalization from
    the real simulate_cluster merge — the rule must flag the merge line
    (taint then floods downstream aggregation; every hit is the same
    code)."""
    src = (REPO / CLUSTER_REL).read_text()
    canonical = "outs = {m: outs[m] for m in sorted(outs)}"
    assert canonical in src
    mutated = src.replace(canonical, "outs = {m: outs[m] for m in outs}")
    root = _tree(tmp_path, {CLUSTER_REL: mutated})
    findings = forksafety.run(root)
    assert findings and _codes(findings) == {"unordered-merge"}
    merge_line = next(
        i for i, ln in enumerate(mutated.splitlines(), start=1)
        if "for m in outs}" in ln
    )
    assert min(f.line for f in findings) == merge_line


# ---------------------------------------------------------------------------
# cbounds
# ---------------------------------------------------------------------------
C_REL = "src/repro/core/_fastsim_c.c"

C_BAD = '''
#include <stdint.h>
#include <stdlib.h>
#include <string.h>

int64_t bad(const int32_t *P, /* (n) request ids */
            int64_t *acc,
            int64_t *out, /* (n) per-request sums */
            int64_t n, int64_t J) {
    int64_t s = 0;
    for (int64_t i = 0; i < n; i++) {
        s += P[i];
        s += acc[i];
    }
    s += out[J];
    int64_t *buf = (int64_t *)malloc((size_t)n * sizeof(int64_t));
    buf[0] = s;
    memset(out, 0, (size_t)J * sizeof(int64_t));
    return s;
}
'''

C_GOOD = '''
#include <stdint.h>
#include <stdlib.h>
#include <string.h>

/* cbounds: O[] < N  -- caller validates object ids */
/* cbounds: slot[] < cap  -- map only holds allocated slots */

int64_t good(const int32_t *O, /* (n) object ids */
             int64_t *slot, /* (N) id->slot map */
             int64_t *acc, /* (cap*J) slot-major accumulators */
             int64_t *hist, /* (hist_len) eviction histogram */
             int64_t n, int64_t N, int64_t cap, int64_t J,
             int64_t hist_len, int64_t n_used) {
    if (n_used >= cap) {
        return -1;
    }
    int64_t s = 0;
    for (int64_t i = 0; i < n; i++) {
        int64_t o = O[i];
        int64_t k = slot[o];
        for (int64_t j = 0; j < J; j++) {
            s += acc[k * J + j];
        }
        hist[s < hist_len ? s : hist_len - 1]++;
    }
    acc[n_used * J] = s;
    int64_t *buf = (int64_t *)malloc((size_t)cap * sizeof(int64_t));
    if (buf == NULL) {
        return -1;
    }
    memset(acc, 0, (size_t)cap * J * sizeof(int64_t));
    free(buf);
    return s;
}
'''


def test_cbounds_trips_on_each_code(tmp_path):
    root = _tree(tmp_path, {C_REL: C_BAD})
    findings = cbounds.run(root)
    assert [f.code for f in findings] == [
        "missing-capacity",      # acc subscripted, no (cap) comment
        "unproved-subscript",    # out[J]: J not tied to n
        "malloc-unchecked",      # buf used before null-check
        "memlen-untied",         # memset length J on an (n)-capacity dest
    ]


def test_cbounds_clean_on_proof_vocabulary(tmp_path):
    """Every evidence form at once: loop bound, guard return, contract
    annotations (value-range), (cap*J) affine compose ``k*J + j``,
    ternary clamp, null-checked malloc, capacity-tied memset."""
    root = _tree(tmp_path, {C_REL: C_GOOD})
    assert cbounds.run(root) == []


def test_cbounds_clean_on_shipped_tree():
    assert cbounds.run(REPO) == []


def test_cbounds_mutation_deleted_guard(tmp_path):
    """ISSUE mutation: disable the slot-growth guard in the real C file
    — the grow-path subscripts and the memset length lose their proof."""
    src = (REPO / C_REL).read_text()
    guard = "if (n_slots == slot_cap) {"
    assert guard in src
    root = _tree(tmp_path, {C_REL: src.replace(guard, "if (0) {")})
    findings = cbounds.run(root)
    assert _codes(findings) == {"memlen-untied", "unproved-subscript"}


def test_cbounds_mutation_deleted_clamp(tmp_path):
    """ISSUE mutation: strip the histogram ternary clamp — the raw
    ``n_ev`` index is unprovable against (hist_len)."""
    src = (REPO / C_REL).read_text()
    clamp = "hist[n_ev < hist_len ? n_ev : hist_len - 1]++;"
    assert clamp in src
    root = _tree(tmp_path, {C_REL: src.replace(clamp, "hist[n_ev]++;")})
    findings = cbounds.run(root)
    assert [f.code for f in findings] == ["unproved-subscript"]


def test_cbounds_mutation_dropped_axiom(tmp_path):
    """Deleting the O[]<N contract annotation must cascade: every
    subscript fed by an object id loses its proof."""
    src = (REPO / C_REL).read_text()
    axiom_line = next(
        ln for ln in src.splitlines()
        if ln.strip().startswith("/* cbounds: O[] < N")
    )
    root = _tree(tmp_path, {C_REL: src.replace(axiom_line, "")})
    findings = cbounds.run(root)
    assert findings
    assert _codes(findings) == {"unproved-subscript"}


# ---------------------------------------------------------------------------
# SARIF emitter
# ---------------------------------------------------------------------------
def test_sarif_structure_and_descriptors():
    from tools.analyze.sarif import SARIF_VERSION, to_sarif

    waivers = load_waivers(WAIVERS_PATH)
    findings = run_rules(REPO, None, waivers)
    doc = to_sarif(findings, RULES)

    assert doc["version"] == SARIF_VERSION == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    (run,) = doc["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "repro-analyze"

    # one descriptor per (rule, code), ids stable and sorted
    ids = [r["id"] for r in driver["rules"]]
    want = sorted(
        f"{name}/{code}"
        for name, mod in RULES.items()
        for code in mod.CODES
    )
    assert ids == want
    assert all(
        set(r) >= {"id", "name", "shortDescription", "defaultConfiguration"}
        for r in driver["rules"]
    )

    # every result points at a declared rule and a real location
    by_id = set(ids)
    for res in run["results"]:
        assert res["ruleId"] in by_id
        (loc,) = res["locations"]
        phys = loc["physicalLocation"]
        assert phys["artifactLocation"]["uriBaseId"] == "SRCROOT"
        assert phys["region"]["startLine"] >= 1
        if res["level"] == "note":
            (sup,) = res["suppressions"]
            assert sup["kind"] == "external"
            assert sup["justification"]
        else:
            assert res["level"] == "error"
            assert "suppressions" not in res

    # the shipped tree: every finding is waived, so no error results
    assert all(r["level"] == "note" for r in run["results"])


def test_sarif_unwaived_finding_is_error():
    from tools.analyze.findings import Finding
    from tools.analyze.sarif import to_sarif

    f = Finding("determinism", "wall-clock", "src/x.py", 3, "time.time()")
    doc = to_sarif([f], RULES)
    (res,) = doc["runs"][0]["results"]
    assert res["level"] == "error"
    assert res["ruleId"] == "determinism/wall-clock"


def test_cli_sarif_output(tmp_path):
    import json as _json

    out = tmp_path / "out.sarif"
    run = subprocess.run(
        [
            sys.executable, "-m", "tools.analyze",
            "--rule", "parity", "--sarif", str(out),
        ],
        cwd=str(REPO),
        capture_output=True,
        text=True,
    )
    assert run.returncode == 0, run.stdout + run.stderr
    doc = _json.loads(out.read_text())
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["tool"]["driver"]["rules"]


# ---------------------------------------------------------------------------
# strict waivers
# ---------------------------------------------------------------------------
def test_cli_strict_waivers_flags_stale(tmp_path):
    stale = tmp_path / "waivers.toml"
    stale.write_text(
        "[[waiver]]\n"
        'rule = "parity"\n'
        'path = "src/repro/core/nonexistent.py"\n'
        'reason = "stale on purpose"\n'
    )
    base = [
        sys.executable, "-m", "tools.analyze",
        "--rule", "parity", "--waivers", str(stale),
    ]
    warn = subprocess.run(
        base, cwd=str(REPO), capture_output=True, text=True
    )
    assert warn.returncode == 0
    assert "unused waiver" in warn.stderr
    strict = subprocess.run(
        base + ["--strict-waivers"],
        cwd=str(REPO),
        capture_output=True,
        text=True,
    )
    assert strict.returncode == 1
    assert "unused waiver" in strict.stderr


def test_cli_strict_waivers_ignores_other_rules_waivers(tmp_path):
    """A waiver for a rule that did NOT run is not stale — running
    ``--rule parity`` must not flag the schema/determinism waivers."""
    run = subprocess.run(
        [
            sys.executable, "-m", "tools.analyze",
            "--rule", "parity", "--strict-waivers",
        ],
        cwd=str(REPO),
        capture_output=True,
        text=True,
    )
    assert run.returncode == 0, run.stdout + run.stderr
