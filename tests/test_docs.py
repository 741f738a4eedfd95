"""Docs gate: the documentation suite must exist, its relative links must
resolve, and docs/telemetry.md must list *exactly* the metrics a
constructed engine registers — name and type — so the reference can never
drift from the code.  `make docs-check` runs this file plus the standalone
link checker; the tier-1 suite collects it too."""
import dataclasses
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

from repro.configs import CONFIGS
from repro.models import LM
from repro.serve import Request, ServeEngine

ROOT = Path(__file__).resolve().parent.parent
DOC = ROOT / "docs" / "telemetry.md"
ROW = re.compile(r"^\|\s*`(serve_\w+)`\s*\|\s*(counter|gauge|histogram)\s*\|",
                 re.M)
SPAN_ROW = re.compile(r"^\|\s*`(serve\.\w+)`\s*\|", re.M)


def test_docs_suite_exists():
    for rel in ("README.md", "docs/serving.md", "docs/telemetry.md"):
        assert (ROOT / rel).is_file(), f"missing {rel}"


def test_markdown_relative_links_resolve():
    res = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "check_docs.py")],
        capture_output=True, text=True, cwd=ROOT)
    assert res.returncode == 0, res.stdout + res.stderr


def _documented():
    return dict(ROW.findall(DOC.read_text()))


def test_telemetry_doc_matches_engine_registry():
    """Two-way check: every documented serve_* metric is registered with
    the documented type, and every metric the engine registers — at
    construction *and* after serving a chunked workload — is documented.
    The engine declares its surface eagerly, so a metric emitted anywhere
    in the serve path but missing from ``_declare_metrics`` shows up here
    as an undocumented stray."""
    doc = _documented()
    assert doc, "no metric rows parsed from docs/telemetry.md"
    cfg = dataclasses.replace(CONFIGS["llama3.2-3b"].reduced(),
                              dtype="float32", num_layers=1)
    lm = LM(cfg)
    eng = ServeEngine(lm, lm.init(jax.random.key(0)), max_batch=2,
                      max_seq=16, cache_backend="paged", page_size=4,
                      prefill_chunk=2)
    registered = {n: m.kind for n, m in eng.reg._metrics.items()
                  if n.startswith("serve_")}
    assert registered == doc, (
        "docs/telemetry.md out of sync with the engine registry:\n"
        f"  undocumented: {sorted(set(registered) - set(doc))}\n"
        f"  stale doc rows: {sorted(set(doc) - set(registered))}\n"
        f"  type mismatches: "
        f"{[n for n in set(doc) & set(registered) if doc[n] != registered[n]]}")
    # drive a chunked workload end-to-end: anything registered lazily on a
    # code path _declare_metrics missed would appear now
    eng.submit(Request(0, np.arange(5, dtype=np.int32) % cfg.vocab_size,
                       max_new_tokens=2))
    eng.run_until_drained()
    after = {n: m.kind for n, m in eng.reg._metrics.items()
             if n.startswith("serve_")}
    assert after == registered, (
        f"metrics registered only at runtime: "
        f"{sorted(set(after) - set(registered))}")


def test_telemetry_doc_lists_every_span_the_engine_emits():
    """The Spans table names exactly the spans a chunked engine and a
    whole-prompt engine emit while serving a request to the end."""
    doc = set(SPAN_ROW.findall(DOC.read_text()))
    assert doc, "no span rows parsed from docs/telemetry.md"
    cfg = dataclasses.replace(CONFIGS["llama3.2-3b"].reduced(),
                              dtype="float32", num_layers=1)
    lm = LM(cfg)
    params = lm.init(jax.random.key(0))
    emitted = set()
    for chunk in (0, 2):
        eng = ServeEngine(lm, params, max_batch=2, max_seq=16,
                          cache_backend="paged", page_size=4,
                          prefill_chunk=chunk)
        eng.submit(Request(0, np.arange(5, dtype=np.int32) % cfg.vocab_size,
                           max_new_tokens=2))
        eng.run_until_drained()
        emitted |= {s.name for s in eng.spans.spans()}
    assert emitted == doc, (
        f"docs/telemetry.md Spans table out of sync with the engine:\n"
        f"  undocumented: {sorted(emitted - doc)}\n"
        f"  stale doc rows: {sorted(doc - emitted)}")
