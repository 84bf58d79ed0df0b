"""bench.py is the host bench: control-plane timings on the host's clock,
no JAX, no device metric. These cases hold it, its Makefile targets, its
committed records and the documents to that."""
import ast
import json
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# flag and make-target suffix -> the one key the lane merges
LANES = {
    "transport": "transport_roundtrip",
    "journal": "journal_wal",
    "fleet": "fleet_scale",
    "weights": "weight_distribution",
}
HOST_ONLY_KEYS = set(LANES.values()) | {"launch_bench", "launch_bench_kube"}
# what the deleted device milestones left behind in documents
STALE = re.compile(
    r"BENCH_r0|MULTICHIP_r0|bench-(?:moe|serving|resize|pp|rl)\b"
    r"|--(?:moe|serving|resize|pipeline|rl)-only\b")
UNCHECKED_DOCS = {"CHANGES.md", "ROADMAP.md", "PERF.md", "ISSUE.md", "REVIEW.md"}


def _read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def _bench_tree():
    return ast.parse(_read("bench.py"))


def _function(tree, name):
    return next(n for n in ast.walk(tree)
                if isinstance(n, ast.FunctionDef) and n.name == name)


def test_bench_imports_no_jax():
    imported = set()
    for node in ast.walk(_bench_tree()):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert "jax" not in imported


@pytest.mark.parametrize("lane", sorted(LANES))
def test_lane_flag_target_and_record_agree(lane):
    tree = _bench_tree()
    # main() maps the flag to the lane's function ...
    flags = {k.value: v.id for n in ast.walk(_function(tree, "main"))
             if isinstance(n, ast.Dict)
             for k, v in zip(n.keys, n.values)
             if isinstance(k, ast.Constant) and isinstance(v, ast.Name)}
    fn = flags[f"--{lane}-only"]
    # ... which runs and merges exactly its own key ...
    call = next(n for n in ast.walk(_function(tree, fn))
                if isinstance(n, ast.Call)
                and getattr(n.func, "id", "") == "_single_lane")
    merge = next(kw.value for kw in call.keywords if kw.arg == "merge_keys")
    assert ast.literal_eval(call.args[0]) == lane
    assert ast.literal_eval(call.args[1]) == (LANES[lane],)
    assert ast.literal_eval(merge) == (LANES[lane],)
    # ... the Makefile's target passes that flag ...
    assert re.search(
        rf"^bench-{lane}:\n\t\$\(PY\) bench\.py --{lane}-only$",
        _read("Makefile"), re.M)
    # ... and the committed record is there, with its trace beside it.
    record = json.loads(_read(".bench_extras.json"))[LANES[lane]]
    assert record["trace_jsonl"] == f".bench_trace/{lane}.jsonl"
    spans = [json.loads(line)["name"]
             for line in _read(record["trace_jsonl"]).splitlines()]
    assert spans == [f"bench.{lane}", f"bench.{LANES[lane]}"]


def test_committed_records_are_host_only():
    extras = json.loads(_read(".bench_extras.json"))
    assert set(extras) <= HOST_ONLY_KEYS
    assert set(LANES.values()) <= set(extras)
    traces = sorted(os.listdir(os.path.join(REPO, ".bench_trace")))
    assert traces == sorted(f"{lane}.jsonl" for lane in LANES)


def _tracked_docs():
    out = subprocess.run(["git", "ls-files", "*.md"], cwd=REPO,
                         capture_output=True, text=True)
    names = out.stdout.split() if out.returncode == 0 else []
    if not names:  # not a checkout: walk the tree
        for dirpath, dirnames, filenames in os.walk(REPO):
            dirnames[:] = [d for d in dirnames if d not in
                           (".git", ".parent", "chiprun_out", ".chip_scratch")]
            names += [os.path.relpath(os.path.join(dirpath, f), REPO)
                      for f in filenames if f.endswith(".md")]
    return sorted(n for n in names if n not in UNCHECKED_DOCS
                  and os.path.exists(os.path.join(REPO, n)))


@pytest.mark.parametrize("doc", _tracked_docs())
def test_document_names_no_deleted_record_or_lane(doc):
    hits = [(i, line.strip()) for i, line in
            enumerate(_read(doc).splitlines(), start=1) if STALE.search(line)]
    assert not hits, f"{doc} still names a deleted record or lane: {hits[:5]}"
