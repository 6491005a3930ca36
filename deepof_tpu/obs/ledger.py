"""Executable ledger + drift math: compile/HLO/memory provenance for
every lowering the framework performs (DESIGN.md "Executable ledger").

The serving and training planes can see latency, SLO burn, and
label-free flow quality, but nothing recorded what each compiled
executable *costs*: an HLO drift (a config edit that silently changed
the lowering), an unexpected recompile (a cache miss where yesterday's
run had a hit), a compile-time blowup, or a memory-footprint jump were
invisible until a bench run happened to catch them. This module makes
each lowering a ledger row — written to ``<log_dir>/ledger.jsonl`` next
to ``metrics.jsonl`` — and makes "did the executables change?" a diff
against a committed baseline ledger instead of a hope.

Per lowering, a row records:

  - a **stable StableHLO fingerprint**: sha256 over the normalized
    ``lowered.as_text()`` (location metadata stripped — the only
    nondeterministic part of the text; the module body, including the
    donation-encoding ``tf.aliasing_output`` attributes, is a pure
    function of (jax version, config, avals, backend)). Same config +
    same jax ⇒ same fingerprint across processes and hosts; any change
    to the computation changes it.
  - **compile wall seconds** and the persistent-cache provenance of the
    compile (requests/hits/misses from train/warmup.py's counters) —
    "this process compiled nothing" stays a checkable fact per
    executable, not per process.
  - **XLA cost analysis**: FLOPs and bytes accessed, their ratio
    (arithmetic intensity), and the nominal roofline seconds one call
    would take at the device's published peak (obs/telemetry.py's
    ``PEAK_BF16_TFLOPS``; None off the table) — the
    drift signal is the COST MODEL, not wall time, because cost
    analysis is deterministic in the lowering while wall time is host
    noise (DESIGN.md has the rationale).
  - **memory_analysis footprint**: argument/output/temp/alias bytes and
    generated code size of the compiled executable (None where the
    backend does not report).
  - the **donation map**: how many of the executable's input leaves are
    donated (buffer reuse) — a lost donation is a silent memory-
    footprint regression even when the HLO is otherwise unchanged.

On top of the rows sits the regression sentinel: :func:`diff_ledgers`
compares a live run's ledger against a committed baseline and yields a
verdict (`fingerprint_drift`, `unexpected_recompiles`,
`compile_blowups`, `memory_growth`) that ``tools/ledger_diff.py`` and
``deepof_tpu tail`` turn into exit code **8** — the same CI-shaped
contract as rc 3–7.

Import discipline: stdlib-only at module import (analyze/tail and the
jax-free diff tool import this); jax is touched only inside the
recording helpers, which always run next to an actual lowering.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import threading
import time
from typing import Any, Callable

from .telemetry import peak_bf16_tflops

#: Ledger schema version (rows carry it; diff refuses nothing on
#: mismatch but reports it — older baselines stay comparable on the
#: fields both sides have).
LEDGER_SCHEMA = 1

#: Every lowering row carries exactly these keys (None where a backend
#: does not report a value) — the schema the fixture test pins.
ROW_KEYS = (
    "kind", "schema", "name", "time", "backend", "fingerprint",
    "hlo_chars", "compile_s", "resolve_s", "compile_kind", "cache_requests",
    "cache_hits", "cache_misses", "cache_verdict", "flops",
    "bytes_accessed", "arith_intensity", "roofline_s", "argument_bytes",
    "output_bytes", "temp_bytes", "alias_bytes", "code_bytes",
    "donated_args", "num_args",
)

# MLIR location metadata is the one part of the printed module that is
# not a pure function of the computation (file paths, line numbers,
# enable-debug-info settings). jax 0.4.x prints without it by default,
# but the fingerprint must not silently change if a caller or a future
# jax turns it on — strip every `loc(...)` attribute (including
# `loc(unknown)` and nested `loc(callsite(...))`/`loc(fused<...>[...])`
# forms, which need balanced-paren scanning, not a regex) and `#loc...`
# definition lines before hashing.
_LOC_LINE = re.compile(r"^#loc\d*\s*=.*$", re.MULTILINE)


def _strip_loc_attrs(text: str) -> str:
    """Remove every `loc(...)` attribute — balanced parens, quote-aware
    (a quoted file name inside a location may itself contain parens),
    token-boundary checked (an identifier merely ending in "loc" is
    kept)."""
    out = []
    i, n = 0, len(text)
    while i < n:
        j = text.find("loc(", i)
        if j == -1:
            out.append(text[i:])
            break
        if j > 0 and (text[j - 1].isalnum() or text[j - 1] in "_$."):
            out.append(text[i:j + 4])
            i = j + 4
            continue
        # drop the attribute plus the whitespace that separated it
        out.append(text[i:j].rstrip(" \t"))
        k = j + 3  # at the opening paren
        depth = 0
        in_str = False
        while k < n:
            c = text[k]
            if in_str:
                if c == "\\":
                    k += 1
                elif c == '"':
                    in_str = False
            elif c == '"':
                in_str = True
            elif c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if depth == 0:
                    break
            k += 1
        i = k + 1
    return "".join(out)


def exec_name(bucket: tuple[int, int], tier: str, mode: str) -> str:
    """The canonical ledger name of a serve-lattice executable — shared
    by `warmup --serve` and the engine so a warmup baseline and a live
    run's rows diff by name: ``serve:<H>x<W>:<tier>:<mode>``."""
    return f"serve:{bucket[0]}x{bucket[1]}:{tier}:{mode}"


def quality_exec_name(bucket: tuple[int, int]) -> str:
    """Ledger name of a bucket's quality-scorer executable (tiers and
    modes share it): ``quality:<H>x<W>``."""
    return f"quality:{bucket[0]}x{bucket[1]}"


def normalize_hlo(text: str) -> str:
    """The fingerprint's input: the StableHLO module text with location
    metadata stripped and line endings normalized. Deliberately keeps
    the module/function names and every attribute that changes the
    compiled artifact (shapes, dtypes, donation aliasing, precision)."""
    text = _strip_loc_attrs(text)
    text = _LOC_LINE.sub("", text)
    return "\n".join(line.rstrip() for line in text.splitlines()).strip()


def fingerprint_text(text: str) -> str:
    """sha256 over the normalized module text, truncated to 16 hex chars
    (64 bits — collision-safe for the dozens of executables a run
    lowers, short enough to eyeball in a report)."""
    norm = normalize_hlo(text)
    return hashlib.sha256(norm.encode()).hexdigest()[:16]


def _cost_analysis(obj) -> dict | None:
    """Flatten `.cost_analysis()` from a Lowered or Compiled object —
    jax returns a dict, a one-element list of dicts, or raises on
    backends without a cost model."""
    try:
        ca = obj.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        return dict(ca) if ca else None
    except Exception:  # noqa: BLE001 - cost model is best-effort
        return None


def _donation(lowered) -> tuple[int | None, int | None]:
    """(donated leaves, total input leaves) from the lowering's
    args_info pytree — the executable's buffer-donation map."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(
            lowered.args_info, is_leaf=lambda a: hasattr(a, "donated"))
        flags = [bool(a.donated) for a in leaves if hasattr(a, "donated")]
        if not flags:
            return None, None
        return sum(flags), len(flags)
    except Exception:  # noqa: BLE001 - provenance is best-effort
        return None, None


def lowering_row(name: str, lowered=None, compiled=None,
                 compile_s: float | None = None,
                 resolve_s: float | None = None,
                 compile_kind: str | None = None,
                 cache: dict | None = None,
                 cache_verdict: str | None = None,
                 backend: str | None = None,
                 fingerprint: str | None = None) -> dict:
    """One ledger row for a lowering. `lowered` (jax.stages.Lowered)
    supplies the fingerprint, cost analysis, and donation map;
    `compiled` (jax.stages.Compiled) supplies memory_analysis — pass
    None where a site has no AOT-compiled object (the train loop's
    jit-dispatch compile) and the fields stay None rather than paying a
    second XLA compile just to fill them. `compile_kind` says what
    compile_s MEASURES — "aot" (pure lower+compile, record_aot),
    "first_step" (the train loop's first-step wall: compile + one
    executed step), "artifact" (fetch/deserialize from the artifact
    store, NO compile at all), or "deep_verify" (the background
    verifier's post-serve re-lowering) — so diff_ledgers never compares
    the units. `cache_verdict` names where the executable came from:
    explicit "artifact_hit" / "index_hit" from the artifact plane, else
    derived from the persistent-cache delta ("hit"/"miss"), else None.
    `fingerprint` sets the row's fingerprint when there is no local
    Lowered to hash (an index-resolved row carries the INDEX's claimed
    fingerprint — what the deep-verify plane later re-checks); it is
    ignored when `lowered` is passed."""
    row: dict[str, Any] = {k: None for k in ROW_KEYS}
    row.update({"kind": "exec", "schema": LEDGER_SCHEMA, "name": name,
                "time": round(time.time(), 3), "backend": backend,
                "fingerprint": fingerprint})
    if compile_s is not None:
        row["compile_s"] = round(float(compile_s), 4)
        row["compile_kind"] = compile_kind
    if resolve_s is not None:
        # the resolution step alone — XLA compile ("aot") or artifact
        # fetch+deserialize ("artifact") — with the shared trace/lower
        # wall excluded; compile_s keeps the historical lower+resolve
        # total so existing baselines stay comparable
        row["resolve_s"] = round(float(resolve_s), 4)
    if cache:
        for k in ("requests", "hits", "misses"):
            if isinstance(cache.get(k), int):
                row[f"cache_{k}"] = cache[k]
    if cache_verdict is not None:
        row["cache_verdict"] = cache_verdict
    elif (row.get("cache_hits") or 0) >= 1:
        row["cache_verdict"] = "hit"
    elif (row.get("cache_misses") or 0) >= 1:
        row["cache_verdict"] = "miss"
    ca = None
    if lowered is not None:
        try:
            text = lowered.as_text()
            row["fingerprint"] = fingerprint_text(text)
            row["hlo_chars"] = len(normalize_hlo(text))
        except Exception:  # noqa: BLE001 - provenance is best-effort
            pass
        ca = _cost_analysis(lowered)
        row["donated_args"], row["num_args"] = _donation(lowered)
    if ca is None and compiled is not None:
        ca = _cost_analysis(compiled)
    if ca:
        flops = float(ca.get("flops", 0.0))
        byt = float(ca.get("bytes accessed", 0.0))
        if flops > 0:
            row["flops"] = flops
            # the time this device at its published peak would take per
            # call: measured wall / roofline_s = per-executable MFU.
            # None on a device outside the peak table (the cpu included)
            peak = peak_bf16_tflops()
            if peak:
                row["roofline_s"] = flops / (peak * 1e12)
        if byt > 0:
            row["bytes_accessed"] = byt
        if flops > 0 and byt > 0:
            row["arith_intensity"] = round(flops / byt, 3)
    if compiled is not None:
        try:
            ma = compiled.memory_analysis()
            for field, key in (("argument_size_in_bytes", "argument_bytes"),
                               ("output_size_in_bytes", "output_bytes"),
                               ("temp_size_in_bytes", "temp_bytes"),
                               ("alias_size_in_bytes", "alias_bytes"),
                               ("generated_code_size_in_bytes",
                                "code_bytes")):
                v = getattr(ma, field, None)
                if isinstance(v, int):
                    row[key] = v
        except Exception:  # noqa: BLE001 - cpu reports it, others may not
            pass
    return row


class ExecutableLedger:
    """Per-run executable ledger: appends one row per lowering to
    ``<log_dir>/ledger.jsonl`` and keeps the ``exec_*`` counter block
    every stats surface exports (heartbeat, /metrics, analyze/tail,
    the fleet scrape — obs/registry.py declares the merge kinds).

    Thread-safe; all hot-path work is `note_exec` (one dict update under
    a lock per already-timed dispatch — the serve bench bounds the whole
    ledger at ≤ 2% of serve p99). File I/O happens only at lowering
    time (compiles dominate it by orders of magnitude) and at flush().
    """

    def __init__(self, log_dir: str | None, enabled: bool = True,
                 backend: str | None = None):
        self.path = (os.path.join(log_dir, "ledger.jsonl")
                     if log_dir and enabled else None)
        self.backend = backend
        self._lock = threading.Lock()
        self._fingerprints: dict[str, str] = {}
        self._lowerings = 0
        self._recompiles = 0
        self._compile_s = 0.0
        self._cache_hits = 0
        self._cache_misses = 0
        # artifact-plane fetch accounting (serve/artifacts.py):
        # hits = executables deserialized instead of compiled,
        # misses = no entry for the local fingerprint (compiled),
        # rejects = entry present but failed an integrity gate (compiled)
        self._artifact_hits = 0
        self._artifact_misses = 0
        self._artifact_rejects = 0
        # executable-index accounting (trace-free resolution):
        # hits = executables resolved with zero trace/lower calls,
        # misses = no index entry for the key (lowering path taken),
        # rejects = entry present but failed a trust gate (forged,
        # cross-wired, stale target, version skew) — loud fallback
        self._index_hits = 0
        self._index_misses = 0
        self._index_rejects = 0
        # deferred deep-verify plane: pending = index-resolved entries
        # awaiting background re-lowering, ok = fingerprint confirmed,
        # demoted = mismatch -> executable swapped for a fresh compile
        self._deep_verify_pending = 0
        self._deep_verify_ok = 0
        self._deep_verify_demoted = 0
        # per-executable measured execution time: name -> [count, total_s,
        # roofline_s] — MFU = roofline / mean measured, re-derived at
        # stats() time, never merged (registry kind: derived)
        self._exec: dict[str, list] = {}

    @property
    def enabled(self) -> bool:
        return self.path is not None

    # ------------------------------------------------------------ record
    def _append(self, row: dict) -> None:
        if self.path is None:
            return
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(self.path, "a") as f:
            f.write(json.dumps(row) + "\n")

    def record(self, name: str, lowered=None, compiled=None,
               compile_s: float | None = None,
               resolve_s: float | None = None,
               compile_kind: str | None = None,
               cache: dict | None = None,
               cache_verdict: str | None = None,
               fingerprint: str | None = None) -> dict:
        """Build, count, and append one lowering row (see lowering_row).
        Returns the row so call sites can fold the fingerprint into
        their own reports (the warmup CLI report does)."""
        row = lowering_row(name, lowered=lowered, compiled=compiled,
                           compile_s=compile_s, resolve_s=resolve_s,
                           compile_kind=compile_kind,
                           cache=cache, cache_verdict=cache_verdict,
                           backend=self.backend, fingerprint=fingerprint)
        with self._lock:
            self._lowerings += 1
            if compile_s is not None:
                self._compile_s += float(compile_s)
            if isinstance(row.get("cache_hits"), int):
                self._cache_hits += row["cache_hits"]
            if isinstance(row.get("cache_misses"), int):
                self._cache_misses += row["cache_misses"]
            fp = row.get("fingerprint")
            if fp is not None:
                prev = self._fingerprints.get(name)
                if prev is not None and prev != fp:
                    # the live recompile signal: the SAME executable name
                    # lowered to a DIFFERENT module within one run
                    self._recompiles += 1
                self._fingerprints[name] = fp
            if row.get("roofline_s") is not None:
                self._exec.setdefault(name, [0, 0.0, 0.0])[2] = \
                    row["roofline_s"]
        if self.enabled:
            self._append(row)
        return row

    def record_aot(self, name: str, lower_fn: Callable[[], Any],
                   artifacts=None) -> Any:
        """The shared AOT helper: time lower_fn() -> Lowered, then
        resolve the executable — from the artifact store when one is
        passed (serve/artifacts.py ArtifactStore, keyed by THIS
        lowering's StableHLO fingerprint, so drifted code always
        misses) and only otherwise by compiling — measure the
        persistent-cache delta of exactly this resolution, and record
        the row: compile_kind "artifact" + cache_verdict "artifact_hit"
        on a fetch, the ordinary "aot" row on a compile (miss, reject,
        or no store). Returns (compiled, row)."""
        from ..train.warmup import cache_delta

        verdict = None
        with cache_delta() as d:
            t0 = time.perf_counter()
            lowered = lower_fn()
            t_res = time.perf_counter()
            compiled = None
            if artifacts is not None:
                try:
                    fp = fingerprint_text(lowered.as_text())
                    compiled, verdict = artifacts.fetch(fp)
                except Exception:  # noqa: BLE001 - store is best-effort
                    compiled, verdict = None, "reject:fetch_failed"
            if compiled is None:
                t_res = time.perf_counter()  # a reject's failed fetch
                #   is not compile wall: resolve_s stays the step that
                #   actually produced the executable
                compiled = lowered.compile()
            dt = time.perf_counter() - t0
            resolve_s = time.perf_counter() - t_res
        hit = verdict == "hit"
        if artifacts is not None:
            with self._lock:
                if hit:
                    self._artifact_hits += 1
                elif verdict == "miss":
                    self._artifact_misses += 1
                else:
                    self._artifact_rejects += 1
        row = self.record(name, lowered=lowered, compiled=compiled,
                          compile_s=dt, resolve_s=resolve_s,
                          compile_kind="artifact" if hit else "aot",
                          cache=d.stats(),
                          cache_verdict="artifact_hit" if hit else None)
        return compiled, row

    def record_index(self, name: str, artifacts, key: str) -> Any:
        """The trace-free resolution helper: resolve `key` through the
        store's executable index (serve/artifacts.py ``resolve`` —
        zero trace/lower calls on every path) and, on a hit, record the
        ``cache_verdict="index_hit"`` row: compile_kind "artifact"
        (resolve_s = compile_s = pure fetch+deserialize wall, and
        diff_ledgers already treats "artifact" rows as non-recompiles),
        fingerprint = the INDEX's claimed fingerprint (there is no
        local Lowered to hash — the deep-verify plane re-checks it
        after serving starts), cost/memory provenance read off the
        deserialized executable. A miss or reject records nothing and
        returns (None, None, verdict): the caller falls back to the
        lowering path, which writes its own row. Returns
        (compiled | None, row | None, verdict)."""
        t0 = time.perf_counter()
        try:
            compiled, fp, verdict = artifacts.resolve(key)
        except Exception:  # noqa: BLE001 - index is best-effort
            compiled, fp, verdict = None, None, "index_reject:resolve_failed"
        dt = time.perf_counter() - t0
        with self._lock:
            if verdict == "index_hit":
                self._index_hits += 1
                self._deep_verify_pending += 1
            elif verdict == "index_miss":
                self._index_misses += 1
            else:
                self._index_rejects += 1
        if compiled is None:
            return None, None, verdict
        row = self.record(name, lowered=None, compiled=compiled,
                          compile_s=dt, resolve_s=dt,
                          compile_kind="artifact",
                          cache_verdict="index_hit",
                          fingerprint=fp)
        return compiled, row, verdict

    def note_deep_verify(self, ok: bool) -> None:
        """One background deep-verify outcome: confirmed (ok) or
        demoted (the index's fingerprint does not match what local code
        lowers to — the executable was swapped for a fresh compile).
        Either way one pending slot drains."""
        with self._lock:
            self._deep_verify_pending = max(
                0, self._deep_verify_pending - 1)
            if ok:
                self._deep_verify_ok += 1
            else:
                self._deep_verify_demoted += 1

    def note_exec(self, name: str, seconds: float) -> None:
        """Accumulate one measured execution of `name` (the serve
        engine's flush timer feeds this; training MFU rides the
        per-record telemetry instead — DESIGN.md) — the denominator of
        the per-executable MFU the stats block derives."""
        with self._lock:
            e = self._exec.setdefault(name, [0, 0.0, 0.0])
            e[0] += 1
            e[1] += float(seconds)

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        """The registry-declared ``exec_*`` block (obs/registry.py owner
        `ledger`): lowering/compile/cache counters, the per-executable
        fingerprint map, and the nominal-roofline MFU over every
        executable with measured executions."""
        with self._lock:
            out: dict[str, Any] = {
                "exec_lowerings": self._lowerings,
                "exec_recompiles": self._recompiles,
                "exec_compile_s": round(self._compile_s, 3),
                "exec_cache_hits": self._cache_hits,
                "exec_cache_misses": self._cache_misses,
                "exec_artifact_hits": self._artifact_hits,
                "exec_artifact_misses": self._artifact_misses,
                "exec_artifact_rejects": self._artifact_rejects,
                "exec_index_hits": self._index_hits,
                "exec_index_misses": self._index_misses,
                "exec_index_rejects": self._index_rejects,
                "exec_deep_verify_pending": self._deep_verify_pending,
                "exec_deep_verify_ok": self._deep_verify_ok,
                "exec_deep_verify_demoted": self._deep_verify_demoted,
                "exec_executables": len(self._fingerprints),
                "exec_fingerprints": dict(self._fingerprints),
                "exec_dispatches": sum(e[0] for e in self._exec.values()),
                "exec_dispatch_s": round(
                    sum(e[1] for e in self._exec.values()), 4),
            }
            # per-executable MFU vs the nominal roofline: how much of
            # the chip's nominal peak the measured dispatches achieved;
            # the max across executables answers "is ANY path near
            # roofline", which survives idle executables at 0
            mfus = [e[2] * e[0] / e[1]
                    for e in self._exec.values()
                    if e[0] > 0 and e[1] > 0 and e[2] > 0]
        out["exec_mfu_nominal"] = (round(max(mfus), 6) if mfus else None)
        return out

    def flush(self) -> None:
        """Append one kind="exec_timing" row per executable with
        measured executions (run end / engine close): the measured
        mean next to the roofline, so offline analysis can re-derive
        MFU without the live process."""
        if not self.enabled:
            return
        with self._lock:
            items = [(n, list(e)) for n, e in self._exec.items()
                     if e[0] > 0]
        for name, (count, total_s, roofline_s) in items:
            mean_s = total_s / count
            self._append({
                "kind": "exec_timing", "schema": LEDGER_SCHEMA,
                "name": name, "time": round(time.time(), 3),
                "count": count, "total_s": round(total_s, 4),
                "mean_s": round(mean_s, 6),
                "mfu_nominal": (round(roofline_s / mean_s, 6)
                                if roofline_s > 0 and mean_s > 0
                                else None)})


# ------------------------------------------------- reading and diffing
# (stdlib-only: analyze/tail and tools/ledger_diff.py run jax-free)


def resolve_ledger_path(path: str) -> str:
    """A ledger argument may be the ledger.jsonl itself or a run dir
    holding one — ONE resolution rule, shared by load_ledger and the
    CLI pre-checks (tail's --ledger-baseline existence check), so the
    gates can never diverge on what counts as a valid ledger path."""
    if os.path.isdir(path):
        return os.path.join(path, "ledger.jsonl")
    return path


def load_ledger(path: str) -> list[dict]:
    """Rows from a ledger.jsonl (or a run dir containing one). Torn
    trailing writes from a killed run are tolerated like metrics.jsonl."""
    path = resolve_ledger_path(path)
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return rows


def latest_by_name(rows: list[dict]) -> dict[str, dict]:
    """{executable name -> newest lowering row}. The newest row wins:
    a re-lowering within a run supersedes the first (the recompile
    itself is visible via exec_recompiles and the diff)."""
    out: dict[str, dict] = {}
    for r in rows:
        if r.get("kind") == "exec" and isinstance(r.get("name"), str):
            out[r["name"]] = r
    return out


def summarize_ledger(rows: list[dict]) -> dict | None:
    """The condensed `exec` block analyze/tail print for a run dir with
    a ledger: totals plus the slowest compiles (the entries worth
    staring at when a cold start got slower). compile_s_total is the
    raw wall the dir's recorded compiles paid, split per compile_kind
    (a dir that held both a `warmup` baseline and a live run mixes
    "aot" and "first_step" units — the split keeps them readable apart,
    exactly as diff_ledgers refuses to compare them); `slowest` is
    newest-row-per-name so a re-lowered executable appears once, with
    its kind."""
    execs = [r for r in rows if r.get("kind") == "exec"]
    if not execs:
        return None
    by_name = latest_by_name(rows)
    recompiles = 0
    seen: dict[str, str] = {}
    for r in execs:
        fp = r.get("fingerprint")
        name = r.get("name")
        if fp and name:
            if name in seen and seen[name] != fp:
                recompiles += 1
            seen[name] = fp
    timings = {r["name"]: r for r in rows
               if r.get("kind") == "exec_timing"
               and isinstance(r.get("name"), str)}
    compile_s = [r["compile_s"] for r in execs
                 if isinstance(r.get("compile_s"), (int, float))]
    by_kind: dict[str, float] = {}
    for r in execs:
        if isinstance(r.get("compile_s"), (int, float)):
            k = r.get("compile_kind") or "unknown"
            by_kind[k] = by_kind.get(k, 0.0) + r["compile_s"]
    out: dict[str, Any] = {
        "lowerings": len(execs),
        "executables": len(by_name),
        "recompiles": recompiles,
        "compile_s_total": round(sum(compile_s), 3) if compile_s else None,
        "compile_s_by_kind": ({k: round(v, 3)
                               for k, v in sorted(by_kind.items())}
                              if by_kind else None),
        "cache_hits": sum(r.get("cache_hits") or 0 for r in execs),
        "cache_misses": sum(r.get("cache_misses") or 0 for r in execs),
        "slowest": [
            {"name": r["name"], "compile_s": r["compile_s"],
             "compile_kind": r.get("compile_kind"),
             "fingerprint": r.get("fingerprint")}
            for r in sorted(
                (r for r in by_name.values()
                 if isinstance(r.get("compile_s"), (int, float))),
                key=lambda r: -r["compile_s"])[:3]],
    }
    mfus = [t["mfu_nominal"] for t in timings.values()
            if isinstance(t.get("mfu_nominal"), (int, float))]
    if mfus:
        out["mfu_nominal_max"] = round(max(mfus), 6)
    return out


#: diff_ledgers' default bounds — overridable from tools/ledger_diff.py
#: and `tail --ledger-*` flags.
DEFAULT_COMPILE_FACTOR = 2.0
DEFAULT_COMPILE_FLOOR_S = 1.0
DEFAULT_MEMORY_FACTOR = 1.2


def _footprint(row: dict) -> int | None:
    vals = [row.get(k) for k in ("argument_bytes", "output_bytes",
                                 "temp_bytes")]
    vals = [v for v in vals if isinstance(v, int)]
    return sum(vals) if vals else None


def diff_ledgers(baseline: list[dict], run: list[dict],
                 compile_factor: float = DEFAULT_COMPILE_FACTOR,
                 compile_floor_s: float = DEFAULT_COMPILE_FLOOR_S,
                 memory_factor: float = DEFAULT_MEMORY_FACTOR) -> dict:
    """The regression sentinel: a live run's ledger vs a committed
    baseline, per executable name (newest row per name on both sides).

    Four failure classes, each a list of {name, baseline, run} entries:

      fingerprint_drift     the HLO changed — the computation is not
                            the one the baseline measured
      unexpected_recompiles the baseline's compile was a persistent-
                            cache hit but this run's missed — a silent
                            cold-start regression (cache key drift,
                            evicted cache, version skew). Rows whose
                            compile_kind is "artifact" (fingerprint- or
                            index-resolved fetches, including
                            cache_verdict="index_hit" rows) or
                            "deep_verify" (the background verifier's
                            post-serve re-lowering) never enter this
                            check on either side: a fetch is not a
                            compile and a deep verify is not a boot, so
                            their cache activity is healthy, not a
                            miss — no spurious rc 8 from booting off
                            the artifact plane
      compile_blowups       compile_s exceeded
                            max(compile_floor_s, baseline * factor) —
                            compared ONLY between rows whose
                            compile_kind matches: a warmup baseline's
                            pure lower+compile ("aot") never bounds a
                            live train run's first-step wall
                            ("first_step" = compile + one executed
                            step), which would fire a false rc 8 on a
                            healthy run
      memory_growth         argument+output+temp bytes exceeded
                            baseline * memory_factor

    `new` / `missing` names are reported but never fail the diff: a
    config can legitimately grow or shrink its lattice, and the warmup
    report covers per-entry coverage. `failed` = any failure-class list
    nonempty — tools/ledger_diff.py and `tail` map it to rc 8.
    """
    base = latest_by_name(baseline)
    live = latest_by_name(run)
    drift, recompiles, blowups, growth = [], [], [], []
    for name in sorted(set(base) & set(live)):
        b, r = base[name], live[name]
        bf, rf = b.get("fingerprint"), r.get("fingerprint")
        if bf and rf and bf != rf:
            drift.append({"name": name, "baseline": bf, "run": rf})
        if (b.get("compile_kind") not in ("artifact", "deep_verify")
                and r.get("compile_kind") not in ("artifact", "deep_verify")
                and (b.get("cache_hits") or 0) >= 1
                and (b.get("cache_misses") or 0) == 0
                and (r.get("cache_misses") or 0) >= 1):
            recompiles.append({
                "name": name,
                "baseline": {"hits": b.get("cache_hits"),
                             "misses": b.get("cache_misses")},
                "run": {"hits": r.get("cache_hits"),
                        "misses": r.get("cache_misses")}})
        bc, rc = b.get("compile_s"), r.get("compile_s")
        if (isinstance(bc, (int, float)) and isinstance(rc, (int, float))
                and b.get("compile_kind") == r.get("compile_kind")
                and rc > max(float(compile_floor_s),
                             bc * float(compile_factor))):
            blowups.append({"name": name, "baseline": bc, "run": rc})
        bm, rm = _footprint(b), _footprint(r)
        if (bm is not None and rm is not None and bm > 0
                and rm > bm * float(memory_factor)):
            growth.append({"name": name, "baseline": bm, "run": rm})
    out = {
        "executables": len(set(base) | set(live)),
        "compared": len(set(base) & set(live)),
        "new": sorted(set(live) - set(base)),
        "missing": sorted(set(base) - set(live)),
        "fingerprint_drift": drift,
        "unexpected_recompiles": recompiles,
        "compile_blowups": blowups,
        "memory_growth": growth,
        "bounds": {"compile_factor": float(compile_factor),
                   "compile_floor_s": float(compile_floor_s),
                   "memory_factor": float(memory_factor)},
    }
    out["failed"] = bool(drift or recompiles or blowups or growth)
    return out


def find_baseline(log_dir: str, explicit: str | None = None) -> str | None:
    """The baseline ledger path for a run dir: an explicit path wins;
    otherwise the committed-by-convention ``<log_dir>/
    ledger_baseline.jsonl`` when present; else None (no verdict)."""
    if explicit:
        return explicit
    cand = os.path.join(log_dir, "ledger_baseline.jsonl")
    return cand if os.path.isfile(cand) else None


def ledger_verdict(log_dir: str, baseline: str | None = None,
                   compile_factor: float = DEFAULT_COMPILE_FACTOR,
                   compile_floor_s: float = DEFAULT_COMPILE_FLOOR_S,
                   memory_factor: float = DEFAULT_MEMORY_FACTOR,
                   run_rows: list[dict] | None = None,
                   base_rows: list[dict] | None = None) -> dict | None:
    """tail/analyze's one-call entry: diff the run dir's ledger.jsonl
    against its baseline (find_baseline), or None when either side is
    absent/unreadable — no ledger, no verdict, never a crash in tail.
    Pass `run_rows`/`base_rows` when the caller already loaded a side
    (tail_summary loads the run's for the condensed block; ledger_drift
    loads the shared baseline once for a whole fleet) so a
    `tail --follow` tick parses each file once, not once per process."""
    path = find_baseline(log_dir, baseline)
    if path is None:
        return None
    try:
        if base_rows is None:
            base_rows = load_ledger(path)
        if run_rows is None:
            run_rows = load_ledger(log_dir)
    except OSError:
        return None
    if not base_rows or not run_rows:
        return None
    return diff_ledgers(base_rows, run_rows,
                        compile_factor=compile_factor,
                        compile_floor_s=compile_floor_s,
                        memory_factor=memory_factor)
