"""The ``repro.analysis`` invariant checker: each rule family catches a
seeded-bad fixture, dispatcher/exempt paths stay clean, suppressions
move findings aside (but keep them auditable), and the real tree is
finding-free.

Fixtures are written to ``tmp_path`` so the full-tree run never sees
them."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

from repro.analysis import run_analysis
from repro.analysis.framework import render_json

REPO = Path(__file__).resolve().parents[1]


def _write(tmp_path: Path, rel: str, src: str) -> Path:
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src)
    return p


def _rules(report):
    return sorted({f.rule for f in report.findings})


# -- RPR1xx: engine affinity ------------------------------------------------


ENGINE_FIXTURE = '''
from repro.core.guard import engine_only

class LiveIndex:
    @engine_only
    def add_text(self, tokens):
        pass

    @engine_only
    def promote_sealed(self, gen, idx):
        pass

class Handlers:
    async def handle_add_bad(self, tokens):
        return self.live.add_text(tokens)            # line 16: flagged

    async def handle_add_ok(self, tokens):
        return await self.batcher.submit_control(
            lambda: self.live.add_text(tokens), "add")

    async def compact_ok(self):
        def _seal():
            self.live.promote_sealed(1, None)        # dispatched: exempt
        await self.batcher.submit_control(_seal, "seal")

    def helper(self, tokens):
        self.live.add_text(tokens)                   # taints helper

    async def handle_indirect_bad(self, tokens):
        self.helper(tokens)                          # flagged via taint
'''


def test_engine_rule_flags_direct_and_indirect_calls(tmp_path):
    _write(tmp_path, "serve/handlers.py", ENGINE_FIXTURE)
    report = run_analysis(["serve"], rules=["RPR1"], root=tmp_path)
    assert _rules(report) == ["RPR101"]
    lines = sorted(f.line for f in report.findings)
    by_line = {f.line: f.message for f in report.findings}
    # the direct call in handle_add_bad
    assert any("handle_add_bad" in m and "add_text" in m
               for m in by_line.values())
    # the indirect call through the tainted helper
    assert any("handle_indirect_bad" in m and "helper" in m
               for m in by_line.values())
    # the helper's own direct call is a finding in its own right
    assert any(m.startswith("serve/handlers.py:Handlers.helper")
               for m in by_line.values())
    # nothing flagged inside the dispatcher-routed paths
    assert all("handle_add_ok" not in m and "compact_ok" not in m
               and "_seal" not in m for m in by_line.values())
    assert len(lines) == 3


def test_engine_rule_only_fires_in_serve_paths(tmp_path):
    # identical code outside a serve/ path: build scripts may mutate
    _write(tmp_path, "tools/handlers.py", ENGINE_FIXTURE)
    report = run_analysis(["tools"], rules=["RPR1"], root=tmp_path)
    assert report.findings == []


# -- RPR2xx: store ordering -------------------------------------------------


STORE_FIXTURE = '''
import numpy as np

def bad_commit_order(writer, root, arrays):
    writer.finalize(num_texts=1, num_windows=1, text_lengths=[1])
    for i, a in enumerate(arrays):
        np.save(root / f"t_{i}.npy", a)

def good_commit_order(writer, root, arrays):
    for i, a in enumerate(arrays):
        np.save(root / f"t_{i}.npy", a)
    writer.finalize(num_texts=1, num_windows=1, text_lengths=[1])

def bad_pointer_write(root):
    (root / "CURRENT").write_text("v000001")

def good_pointer_write(root):
    tmp = root / "CURRENT.tmp"
    tmp.write_text("v000001")
    tmp.rename(root / "CURRENT")
'''


def test_store_rules_flag_bad_order_and_raw_pointer_writes(tmp_path):
    _write(tmp_path, "pkg/writer.py", STORE_FIXTURE)
    report = run_analysis(["pkg"], rules=["RPR201", "RPR202"],
                          root=tmp_path)
    msgs = {f.rule: [] for f in report.findings}
    for f in report.findings:
        msgs[f.rule].append(f.message)
    assert sorted(msgs) == ["RPR201", "RPR202"]
    assert any("bad_commit_order" in m for m in msgs["RPR201"])
    assert all("good_commit_order" not in m for m in msgs["RPR201"])
    # the raw write is flagged; the tmp+rename one is not
    lines202 = [f.line for f in report.findings if f.rule == "RPR202"]
    assert len(lines202) == 1


def test_store_module_itself_is_exempt_from_rpr202(tmp_path):
    _write(tmp_path, "src/repro/core/store.py",
           '(root / "CURRENT").write_text("v1")\n')
    report = run_analysis(["src"], rules=["RPR202"], root=tmp_path)
    assert report.findings == []


FSIO_FIXTURE = '''
import shutil
import numpy as np
from repro.fault import fsio

def raw_manifest(root, payload):
    np.save(root / "t_00.keys.npy", payload)         # RPR203: raw np.save
    (root / "manifest.json").write_text("{}")        # RPR203 (+RPR202)

def raw_cleanup(root):
    (root / "shard_0.pkl").unlink()                  # RPR203: .pkl unlink
    shutil.rmtree(root / "old", ignore_errors=True)  # no artifact: clean

def routed(root, payload):
    fsio.np_save(root / "t_00.keys.npy", payload, site="x.arr")
    fsio.commit_text(root / "manifest.json", "{}", site="x.manifest")
    fsio.unlink(root / "shard_0.pkl", site="x.retire")

def not_a_rename(s):
    return s.replace("old", "new")                   # str.replace: clean
'''


def test_fsio_rule_flags_bypasses_and_accepts_routed_calls(tmp_path):
    _write(tmp_path, "pkg/mutators.py", FSIO_FIXTURE)
    report = run_analysis(["pkg"], rules=["RPR203"], root=tmp_path)
    lines = sorted(f.line for f in report.findings)
    src = FSIO_FIXTURE.splitlines()
    flagged = {src[ln - 1].strip() for ln in lines}
    assert len(lines) == 3
    assert any("np.save" in s for s in flagged)
    assert any("manifest.json" in s and "write_text" in s for s in flagged)
    assert any(".pkl" in s for s in flagged)
    # fsio-routed calls, artifact-free rmtree, and str.replace are clean
    assert all("fsio." not in s for s in flagged)
    assert all("shutil.rmtree" not in s for s in flagged)
    assert all("not_a_rename" not in s for s in flagged)


def test_fsio_rule_enforces_every_mutation_in_durability_modules(tmp_path):
    # inside an enforced module even artifact-free mutations must route
    # through fsio
    _write(tmp_path, "src/repro/train/checkpoint.py",
           'import shutil\n'
           'def gc(p):\n'
           '    shutil.rmtree(p, ignore_errors=True)\n')
    report = run_analysis(["src"], rules=["RPR203"], root=tmp_path)
    assert [f.line for f in report.findings] == [3]
    # the fsio module itself is exempt (it IS the indirection)
    _write(tmp_path, "src/repro/fault/fsio.py",
           'def write_bytes(path, data, *, site):\n'
           '    path.write_bytes(data)\n')
    report = run_analysis(["src/repro/fault"], rules=["RPR203"],
                          root=tmp_path)
    assert report.findings == []


# -- RPR3xx: kernel purity --------------------------------------------------


KERNEL_FIXTURE = '''
import numpy as np
from functools import partial
import jax.experimental.pallas as pl

def _sum_kernel(x_ref, o_ref, *, block):
    total = np.sum(x_ref[...])                       # RPR301
    if total > 0:                                    # RPR303 (traced)
        o_ref[...] = total
    host = total.item()                              # RPR302

def clean_body(x_ref, o_ref, *, block):
    i = pl.program_id(0)
    o_ref[...] = x_ref[...] * 2

def run(x):
    return pl.pallas_call(partial(clean_body, block=8))(x)

def host_helper(arr):
    if arr.size > 0:                                 # not a kernel: fine
        return np.sum(arr)
'''


def test_kernel_rules_flag_numpy_sync_and_traced_branch(tmp_path):
    _write(tmp_path, "kernels/bad.py", KERNEL_FIXTURE)
    report = run_analysis(["kernels"], root=tmp_path)
    assert _rules(report) == ["RPR301", "RPR302", "RPR303"]
    assert all("_sum_kernel" in f.message for f in report.findings)


def test_kernel_rules_scope_to_kernels_dirs(tmp_path):
    _write(tmp_path, "models/bad.py", KERNEL_FIXTURE)
    report = run_analysis(["models"], rules=["RPR3"], root=tmp_path)
    assert report.findings == []


# -- RPR4xx: API deprecations -----------------------------------------------


API_FIXTURE = '''
from repro.core.index import AlignmentIndex          # RPR403

def old_style(aligner, qs):
    res = aligner.find_batch(qs, 0.5, backend="exact")  # RPR401
    raw = aligner.find(qs[0], 0.5, legacy_tuples=True)           # RPR402
    idx = AlignmentIndex(scheme=None)                # RPR403
    return res, raw, idx

def new_style(aligner, qs, opts):
    return aligner.find_batch(qs, 0.5, options=opts)

def core_function_old(index, qs):
    from repro.core import batch_query
    return batch_query(index, qs, 0.5, sketch_backend="exact")  # RPR404

def core_function_ok(index, qs, opts):
    from repro.core import batch_query
    return batch_query(index, qs, 0.5, options=opts)
'''


def test_api_rules_flag_each_deprecated_surface(tmp_path):
    _write(tmp_path, "pkg/old.py", API_FIXTURE)
    report = run_analysis(["pkg"], root=tmp_path)
    assert _rules(report) == ["RPR401", "RPR402", "RPR403", "RPR404"]
    assert sum(f.rule == "RPR403" for f in report.findings) == 2
    assert all("new_style" not in f.message for f in report.findings)


def test_rpr404_method_calls_defer_overlap_to_rpr401(tmp_path):
    # on a *method* call RPR401 owns backend/fanout/sketches; RPR404
    # adds only the spellings RPR401 cannot see (sketch_backend, and any
    # stage kwarg on a bare-function call) so one call site never earns
    # two findings for the same kwarg
    _write(tmp_path, "pkg/mixed.py", '''
def f(aligner, idx, qs, sk):
    from repro.core import batch_query
    aligner.find_batch(qs, 0.5, sketches=sk)               # RPR401 only
    aligner.find_batch(qs, 0.5, sketch_backend="exact")    # RPR404 only
    batch_query(idx, qs, 0.5, sketch_backend="exact", sketches=sk)  # RPR404
''')
    report = run_analysis(["pkg"], rules=["RPR4"], root=tmp_path)
    assert _rules(report) == ["RPR401", "RPR404"]
    by_line = {f.line: f.rule for f in report.findings}
    assert by_line == {4: "RPR401", 5: "RPR404", 6: "RPR404"}


# -- suppressions, parse errors, CLI ----------------------------------------


def test_allow_comment_suppresses_but_stays_auditable(tmp_path):
    _write(tmp_path, "pkg/waived.py", '''
def bad(root):
    (root / "CURRENT").write_text("v1")  # repro: allow[RPR202]

def bad_above(root):
    # repro: allow[RPR202]
    (root / "CURRENT").write_text("v2")

def bad_unwaived(root):
    (root / "CURRENT").write_text("v3")

def bad_wrong_rule(root):
    (root / "CURRENT").write_text("v4")  # repro: allow[RPR999]

def bad_wildcard(root):
    (root / "CURRENT").write_text("v5")  # repro: allow[*]
''')
    report = run_analysis(["pkg"], rules=["RPR202"], root=tmp_path)
    assert len(report.findings) == 2          # unwaived + wrong-rule
    assert len(report.suppressed) == 3        # same-line, above, wildcard
    # suppressed findings stay in the JSON artifact for audit
    payload = json.loads(render_json(report))
    assert len(payload["suppressed"]) == 3
    assert payload["checked_files"] == 1


def test_syntax_errors_surface_as_findings(tmp_path):
    _write(tmp_path, "pkg/broken.py", "def f(:\n")
    report = run_analysis(["pkg"], root=tmp_path)
    assert [f.rule for f in report.findings] == ["RPR000"]


def test_cli_exit_codes_and_json(tmp_path):
    _write(tmp_path, "pkg/bad.py",
           '(root / "CURRENT").write_text("v1")\n')
    env = {"PYTHONPATH": str(REPO / "src")}
    bad = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--format", "json", "pkg"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert bad.returncode == 1
    payload = json.loads(bad.stdout)
    assert payload["findings"][0]["rule"] == "RPR202"
    assert "RPR101" in payload["rules"]       # every family documented
    ok = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--rules", "RPR3", "pkg"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert ok.returncode == 0, ok.stdout


# -- the real tree is clean -------------------------------------------------


def test_repository_tree_has_zero_findings():
    paths = [p for p in ("src", "tests", "benchmarks", "examples")
             if (REPO / p).exists()]
    report = run_analysis(paths, root=REPO)
    assert report.findings == [], "\n".join(
        f.render() for f in report.findings)
    # the waivers on deprecation/corruption tests stay visible
    assert report.suppressed, "expected audited allow[] waivers"
