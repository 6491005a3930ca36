"""Executable artifact plane: fingerprint-keyed AOT executables on disk
(DESIGN.md "Artifact plane").

Scale-up latency is the fleet's dominant tax: every respawn and
autoscale-up pays the full (bucket x tier x mode) lattice compile,
because the r06 heap-corruption finding forces the persistent XLA cache
OFF for concurrent cpu children. This module replaces the *compile*
with a *fetch*: ``warmup --serve`` (the single writer) serializes each
AOT-compiled executable (``jax.experimental.serialize_executable``)
under ``artifacts/exec/<stablehlo-fingerprint>/`` next to an
atomic-rename manifest, and every engine/replica start deserializes
instead of compiling.

Integrity model — the fingerprint IS the gate:

  - The store key is the StableHLO fingerprint of the *local* lowering
    (obs/ledger.py ``fingerprint_text``), recomputed by every consumer
    at fetch time. Drifted code lowers to different StableHLO, which
    hashes to a different key, which is a store MISS — a stale artifact
    can never load against changed code.
  - The manifest must agree with its own directory name, the payload's
    size/crc32, the backend, and the jax version; any mismatch is a
    loud ``reject:*`` (stderr warn + ledger counter) and the caller
    falls back to the ordinary compile path.
  - Publish stages into a ``.tmp-<pid>-*`` sibling and ``os.rename``s
    the whole directory into place: readers never observe a torn entry,
    and concurrent publishers resolve first-writer-wins (renaming onto
    an existing entry fails, which is "exists", not an error).

Executable index — trace-free resolution (this file's second plane):

  Recomputing the fingerprint means re-tracing and re-lowering every
  lattice executable at boot, which is the dominant cold-boot cost
  (lowering is host-bound even on real devices). ``index.json`` in the
  store root maps a pure, jax-free **resolution key** — sha256-16 over
  (exec name, config digest, aval signature, backend, jax version) —
  to the fingerprint the single writer lowered for that key. A
  consumer that resolves through the index performs zero trace/lower
  calls: key lookup, structural gates, ``fetch`` (manifest + crc), and
  deserialize. What the index loses relative to the fingerprint path
  is the drifted-code guarantee: code drift changes the fingerprint
  but not the key, so a stale index can serve a stale executable whose
  bytes are intact. That gap is closed by the **deferred deep-verify
  plane** (serve/engine.py): a background verifier re-lowers each
  index-resolved entry *after* serving starts and loudly demotes on
  fingerprint mismatch (counter + warn record + recompile swap-in).
  Forged or torn index state never resolves silently: every entry
  must hash back to its own key, name-match its target's manifest, and
  pass the same backend/jax/crc gates as a fingerprint fetch —
  anything else is a counted ``index_reject`` and the caller falls
  back to the compile path.

Single-writer publish + read-only consumers is exactly the discipline
the cross-process persistent-cache corruption violated; the artifact
plane gets warm starts on this host without reopening that wound.

Import discipline: module import is stdlib-only so the ``deepof_tpu
artifacts`` CLI verb (list/verify/gc) stays jax-free; jax is imported
inside the serialize/deserialize helpers only.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
import time
import zlib

#: manifest schema version — bumped on any layout change so old entries
#: reject (schema mismatch) instead of deserializing garbage
SCHEMA = 1

#: index schema version — bumped when resolution-key composition or the
#: entry layout changes, so old indexes miss loudly instead of mapping
#: keys built one way to fingerprints recorded another
INDEX_SCHEMA = 1

MANIFEST = "manifest.json"
BLOB = "exec.bin"
INDEX = "index.json"

#: default store root: lives under artifacts/ with the other
#: cross-session state (hostmesh.COMPILE_CACHE_DIR convention) so one
#: rsync of artifacts/ carries warm executables to a fresh host
DEFAULT_STORE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "artifacts", "exec")


def _is_fingerprint(name: str) -> bool:
    return (len(name) == 16
            and all(c in "0123456789abcdef" for c in name))


def store_entries(root: str) -> list[str]:
    """Fingerprint directory names present in the store (sorted); tmp
    staging dirs and strangers are not entries."""
    try:
        names = os.listdir(root)
    except OSError:
        return []
    return sorted(n for n in names
                  if _is_fingerprint(n)
                  and os.path.isdir(os.path.join(root, n)))


def verify_entry(root: str, fingerprint: str) -> dict:
    """Structural verdict for one entry — manifest parses, fingerprint
    agrees with the directory name, payload size/crc32 agree with the
    manifest. jax-free: deserialization is NOT attempted here (that is
    the consumer's job, behind the same gates plus backend/version)."""
    entry = {"fingerprint": fingerprint, "ok": False, "why": None,
             "name": None, "backend": None, "size": None, "crc32": None,
             "created": None}
    d = os.path.join(root, fingerprint)
    man_path = os.path.join(d, MANIFEST)
    try:
        with open(man_path) as f:
            man = json.load(f)
    except (OSError, ValueError) as e:
        entry["why"] = f"manifest_unreadable: {e}"
        return entry
    entry["name"] = man.get("name")
    entry["backend"] = man.get("backend")
    entry["created"] = man.get("created")
    if man.get("schema") != SCHEMA:
        entry["why"] = f"schema_mismatch: {man.get('schema')!r}"
        return entry
    if man.get("fingerprint") != fingerprint:
        entry["why"] = (f"fingerprint_mismatch: manifest says "
                        f"{man.get('fingerprint')!r}")
        return entry
    payload = man.get("payload") or {}
    blob_path = os.path.join(d, payload.get("file") or BLOB)
    try:
        blob = open(blob_path, "rb").read()
    except OSError as e:
        entry["why"] = f"payload_unreadable: {e}"
        return entry
    entry["size"] = len(blob)
    entry["crc32"] = zlib.crc32(blob)
    if payload.get("size") != len(blob):
        entry["why"] = (f"size_mismatch: manifest {payload.get('size')} "
                        f"!= {len(blob)}")
        return entry
    if payload.get("crc32") != entry["crc32"]:
        entry["why"] = "crc_mismatch"
        return entry
    entry["ok"] = True
    return entry


def verify_store(root: str) -> dict:
    """Whole-store structural report: every entry's verdict plus the
    corrupt/ok split and leftover tmp staging dirs (a publisher that
    died mid-stage)."""
    fps = store_entries(root)
    entries = [verify_entry(root, fp) for fp in fps]
    tmp = []
    try:
        tmp = sorted(n for n in os.listdir(root) if n.startswith(".tmp-"))
    except OSError:
        pass
    return {
        "dir": root,
        "entries": entries,
        "total": len(entries),
        "ok": sum(1 for e in entries if e["ok"]),
        "corrupt": [e["fingerprint"] for e in entries if not e["ok"]],
        "tmp_dirs": tmp,
    }


def gc_store(root: str, older_than_days: float | None = None,
             roots: set[str] | frozenset[str] | None = None) -> dict:
    """Garbage-collect the store: corrupt entries and orphaned tmp
    staging always go; with ``older_than_days`` set, structurally
    valid entries whose manifest ``created`` stamp is older also go
    (code churn strands entries forever — their fingerprints never
    recur — so age is the only useful liveness signal).

    ``roots`` pins fingerprints against *age-based* removal (corrupt
    entries are removed regardless — they cannot serve). The index's
    own targets are always added to the root set, so a GC triggered by
    supervisor retirement can never collect an executable the next
    replica boot would index-resolve. Index entries whose target no
    longer exists after the sweep are pruned from ``index.json``."""
    report = verify_store(root)
    pinned = set(roots or ())
    pinned |= index_targets(root)
    removed, kept = [], []
    now = time.time()
    for e in report["entries"]:
        drop = not e["ok"]
        if (not drop and older_than_days is not None
                and e["fingerprint"] not in pinned
                and isinstance(e["created"], (int, float))
                and now - e["created"] > older_than_days * 86400.0):
            drop = True
        if drop:
            shutil.rmtree(os.path.join(root, e["fingerprint"]),
                          ignore_errors=True)
            removed.append(e["fingerprint"])
        else:
            kept.append(e["fingerprint"])
    for t in report["tmp_dirs"]:
        p = os.path.join(root, t)
        if os.path.isdir(p):
            shutil.rmtree(p, ignore_errors=True)
        else:
            try:
                os.remove(p)
            except OSError:
                pass
    pruned = _prune_index(root, set(removed))
    return {"dir": root, "removed": removed, "kept": kept,
            "tmp_removed": report["tmp_dirs"], "index_pruned": pruned}


# ------------------------------------------------- executable index


def resolution_key(name: str, config_digest: str, aval_sig: str,
                   backend: str, jax_version: str) -> str:
    """The pure, jax-free index key: sha256-16 over the canonical JSON
    of the five components. Deterministic across processes (sorted
    keys, no whitespace variance), recomputable by any consumer that
    knows its own config + concrete param shapes — no tracing."""
    payload = json.dumps(
        [name, config_digest, aval_sig, backend, jax_version],
        sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


#: config fields that flow into a serve lowering — anything else
#: (ports, log dirs, fleet knobs) varies per replica without changing
#: the StableHLO, and must NOT invalidate the index
def serve_config_digest(cfg) -> str:
    """Digest of the lowering-relevant config subset. Jax-free: reads
    dataclass fields only. A change to any field that shapes the
    lattice (model topology, buckets, batch, tiers, warm session) flips
    the digest, so the index misses loudly and the consumer falls back
    to the compile path."""
    sub = {
        "model": cfg.model,
        "width_mult": cfg.width_mult,
        "corr_max_disp": cfg.corr_max_disp,
        "corr_stride": cfg.corr_stride,
        "time_step": cfg.data.time_step,
        "image_size": list(cfg.data.image_size),
        "max_batch": cfg.serve.max_batch,
        "buckets": [list(b) for b in (cfg.serve.buckets or ())],
        "precisions": list(cfg.serve.precisions or ()),
        "warm_start": cfg.serve.session.warm_start,
        "warm_width": cfg.serve.session.warm_width,
    }
    payload = json.dumps(sub, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def _empty_index() -> dict:
    return {"schema": INDEX_SCHEMA, "updated": None, "entries": {}}


def load_index(root: str) -> dict:
    """Read ``index.json`` tolerantly: absent, torn, or wrong-schema
    index reads as empty (= every resolve is an index miss, never an
    exception on the boot path)."""
    path = os.path.join(root, INDEX)
    try:
        with open(path) as f:
            idx = json.load(f)
    except (OSError, ValueError):
        return _empty_index()
    if (not isinstance(idx, dict)
            or idx.get("schema") != INDEX_SCHEMA
            or not isinstance(idx.get("entries"), dict)):
        return _empty_index()
    return idx


def write_index(root: str, entries: dict) -> dict:
    """Single-writer atomic index publish: merge ``entries`` (key ->
    entry dict) over the existing index, stage to a ``.tmp-`` sibling
    file, ``os.rename`` over ``index.json``. Readers observe either
    the old or the new index, never a torn one."""
    idx = load_index(root)
    idx["entries"].update(entries)
    idx["updated"] = time.time()
    os.makedirs(root, exist_ok=True)
    tmp = os.path.join(root, f".tmp-{os.getpid()}-index.json")
    with open(tmp, "w") as f:
        json.dump(idx, f, indent=2, sort_keys=True)
    os.rename(tmp, os.path.join(root, INDEX))
    return idx


def index_targets(root: str) -> set[str]:
    """Fingerprints the index maps to (jax-free) — the GC root set a
    supervisor pins before sweeping the store."""
    idx = load_index(root)
    out = set()
    for ent in idx["entries"].values():
        fp = (ent or {}).get("fingerprint")
        if isinstance(fp, str) and _is_fingerprint(fp):
            out.add(fp)
    return out


def _prune_index(root: str, removed_fps: set[str]) -> list[str]:
    """Drop index entries whose target fingerprint was just GC'd, so a
    later boot takes a clean index MISS instead of a stale-target
    reject. No-op when there is no index or nothing points at the
    removed set."""
    if not removed_fps or not os.path.isfile(os.path.join(root, INDEX)):
        return []
    idx = load_index(root)
    stale = [k for k, ent in idx["entries"].items()
             if (ent or {}).get("fingerprint") in removed_fps]
    if not stale:
        return []
    for k in stale:
        del idx["entries"][k]
    idx["updated"] = time.time()
    tmp = os.path.join(root, f".tmp-{os.getpid()}-index.json")
    with open(tmp, "w") as f:
        json.dump(idx, f, indent=2, sort_keys=True)
    os.rename(tmp, os.path.join(root, INDEX))
    return sorted(stale)


# --------------------------------------------------------- jax half


def _serialize_compiled(compiled) -> bytes:
    """One self-contained blob per executable: the PJRT payload plus the
    pickled in/out tree defs ``deserialize_and_load`` needs. Local
    trusted store (single writer = this repo's own warmup), so pickle
    for the tree defs is acceptable."""
    import pickle

    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = se.serialize(compiled)
    return pickle.dumps((payload, in_tree, out_tree))


def _deserialize_compiled(blob: bytes, device):
    """Load a published executable onto `device`. Every published
    executable is a single-device lowering; left to its default,
    `deserialize_and_load` spreads the load over ALL devices of the
    backend, and a one-device executable then fails at its first call on
    any host with more than one ("expected ... N shards")."""
    import pickle

    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = pickle.loads(blob)
    return se.deserialize_and_load(payload, in_tree, out_tree,
                                   execution_devices=[device])


def params_aval_sig(params, extra: tuple = ()) -> str:
    """Aval signature over a params tree plus explicit extra avals —
    sha256-16 of the sorted (tree path, shape, dtype) list. Reading
    ``.shape``/``.dtype`` off concrete arrays (engine side) or
    ShapeDtypeStructs (warmup side) is NOT a trace, so both sides
    compute the identical signature trace-free. A checkpoint whose
    shapes disagree with the published lattice (width drift, dtype
    drift) flips the signature and the index misses instead of serving
    an executable lowered for different avals.

    ``extra`` is a tuple of (label, shape-tuple, dtype-str) triples for
    non-params inputs (the batched frame-pair aval)."""
    from jax.tree_util import keystr, tree_flatten_with_path

    flat, _ = tree_flatten_with_path(params)
    rows = [[keystr(p), list(v.shape), str(v.dtype)] for p, v in flat]
    rows += [[label, list(shape), str(dtype)]
             for label, shape, dtype in extra]
    rows.sort()
    payload = json.dumps(rows, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


class ArtifactStore:
    """Fingerprint-keyed executable store bound to one backend and, for
    its consumers, to the one device fetched executables are loaded onto
    (the engine's; a store that only publishes or verifies files needs
    none).

    ``fetch(fingerprint)`` -> ``(compiled | None, verdict)`` where
    verdict is ``"hit"``, ``"miss"`` (no entry — including every
    drifted-code case, because the caller keys by its OWN lowering's
    fingerprint), or ``"reject:<why>"`` (entry exists but failed an
    integrity gate; warned loudly on stderr). ``publish`` is the single
    writer's atomic-rename path and returns ``"published"`` /
    ``"exists"`` / ``"error:<why>"`` — never raises into the warmup.
    """

    def __init__(self, root: str, backend: str | None = None, device=None):
        self.root = str(root)
        self.backend = backend
        self.device = device
        self._index = None  # lazy; one load per store instance

    # consumers ------------------------------------------------------

    def resolve(self, key: str):
        """Trace-free resolution: index key -> ``(compiled | None,
        fingerprint | None, verdict)`` with verdict one of
        ``"index_hit"`` / ``"index_miss"`` / ``"index_reject:<why>"``.
        Zero trace/lower calls on every path — lookup, structural
        gates, manifest/crc fetch, deserialize. Every reject falls back
        loudly (stderr + counted by the ledger); a forged entry (does
        not hash back to its own key), a cross-wired entry (target
        manifest name disagrees), or a stale target (entry GC'd) never
        resolves silently."""
        if self._index is None:
            self._index = load_index(self.root)
        ent = self._index["entries"].get(key)
        if ent is None:
            return None, None, "index_miss"
        try:
            want = resolution_key(ent["name"], ent["config_digest"],
                                  ent["aval_sig"], ent["backend"],
                                  ent["jax"])
        except (KeyError, TypeError):
            return None, None, self._index_reject(key, "entry_malformed")
        if want != key:
            return None, None, self._index_reject(
                key, f"entry_forged: components hash to {want}")
        fp = ent.get("fingerprint")
        if not (isinstance(fp, str) and _is_fingerprint(fp)):
            return None, None, self._index_reject(
                key, f"bad_fingerprint: {fp!r}")
        import jax
        if self.backend and ent["backend"] != self.backend:
            return None, fp, self._index_reject(
                key, f"backend_mismatch: index entry is for "
                     f"{ent['backend']!r}, we run {self.backend!r}")
        if ent["jax"] != jax.__version__:
            return None, fp, self._index_reject(
                key, f"jax_version_mismatch: index entry from "
                     f"{ent['jax']!r}, we run {jax.__version__!r}")
        if not os.path.isfile(os.path.join(self.root, fp, MANIFEST)):
            return None, fp, self._index_reject(
                key, f"stale_target: {fp} not in store")
        try:
            with open(os.path.join(self.root, fp, MANIFEST)) as f:
                man = json.load(f)
        except (OSError, ValueError) as e:
            return None, fp, self._index_reject(
                key, f"target_manifest_unreadable: {e}")
        if man.get("name") != ent["name"]:
            return None, fp, self._index_reject(
                key, f"name_mismatch: target manifest says "
                     f"{man.get('name')!r}, index entry is "
                     f"{ent['name']!r}")
        compiled, verdict = self.fetch(fp)
        if compiled is None:
            why = verdict.split(":", 1)[1] if ":" in verdict else verdict
            return None, fp, self._index_reject(key, f"target_{why}")
        return compiled, fp, "index_hit"

    def index_entry(self, key: str) -> dict | None:
        """The raw index entry for a key (for deep-verify metadata like
        ``prior_hw``), or None. Uses the same lazily-loaded snapshot as
        ``resolve``."""
        if self._index is None:
            self._index = load_index(self.root)
        return self._index["entries"].get(key)

    @staticmethod
    def _index_reject(key: str, why: str) -> str:
        print(f"artifacts: INDEX REJECT {key}: {why} — falling back to "
              f"the lowering path", file=sys.stderr)
        return f"index_reject:{why.split(':', 1)[0]}"

    def fetch(self, fingerprint: str):
        d = os.path.join(self.root, fingerprint)
        if not os.path.isfile(os.path.join(d, MANIFEST)):
            return None, "miss"
        entry = verify_entry(self.root, fingerprint)
        if not entry["ok"]:
            return None, self._reject(fingerprint, entry["why"])
        with open(os.path.join(d, MANIFEST)) as f:
            man = json.load(f)
        import jax
        if self.backend and man.get("backend") != self.backend:
            return None, self._reject(
                fingerprint, f"backend_mismatch: artifact is for "
                             f"{man.get('backend')!r}, we run "
                             f"{self.backend!r}")
        if man.get("jax") != jax.__version__:
            return None, self._reject(
                fingerprint, f"jax_version_mismatch: artifact from "
                             f"{man.get('jax')!r}, we run "
                             f"{jax.__version__!r}")
        blob_path = os.path.join(d, (man.get("payload") or {}).get("file")
                                 or BLOB)
        try:
            with open(blob_path, "rb") as f:
                compiled = _deserialize_compiled(
                    f.read(), self.device or jax.local_devices()[0])
        except Exception as e:  # noqa: BLE001 - any failure = fall back
            return None, self._reject(fingerprint,
                                      f"deserialize_failed: {e}")
        return compiled, "hit"

    @staticmethod
    def _reject(fingerprint: str, why: str) -> str:
        print(f"artifacts: REJECT {fingerprint}: {why} — falling back "
              f"to compile", file=sys.stderr)
        return f"reject:{why.split(':', 1)[0]}"

    # the single writer ----------------------------------------------

    def publish(self, fingerprint: str, compiled, *, name: str = "",
                compile_s: float | None = None, meta: dict | None = None
                ) -> str:
        final = os.path.join(self.root, fingerprint)
        if os.path.isfile(os.path.join(final, MANIFEST)):
            return "exists"
        try:
            import jax

            blob = _serialize_compiled(compiled)
            man = {
                "schema": SCHEMA,
                "fingerprint": fingerprint,
                "name": name,
                "backend": self.backend or jax.default_backend(),
                "jax": jax.__version__,
                "compile_s": compile_s,
                "created": time.time(),
                "payload": {"file": BLOB, "size": len(blob),
                            "crc32": zlib.crc32(blob)},
            }
            if meta:
                man.update(meta)
            tmp = os.path.join(self.root,
                               f".tmp-{os.getpid()}-{fingerprint}")
            os.makedirs(tmp, exist_ok=True)
            with open(os.path.join(tmp, BLOB), "wb") as f:
                f.write(blob)
            with open(os.path.join(tmp, MANIFEST), "w") as f:
                json.dump(man, f, indent=2, sort_keys=True)
            try:
                os.rename(tmp, final)
            except OSError:
                # someone else won the rename race; their entry stands
                shutil.rmtree(tmp, ignore_errors=True)
                return "exists"
            return "published"
        except Exception as e:  # noqa: BLE001 - publish is best-effort
            print(f"artifacts: publish {fingerprint} failed: {e}",
                  file=sys.stderr)
            return f"error:{type(e).__name__}"


def store_for_config(cfg, device=None) -> ArtifactStore | None:
    """The store a serve config asks for, or None when the plane is off
    (``serve.artifacts_dir`` empty). Resolved to an absolute path so
    replica subprocesses (their own cwd) read the same store. `device`:
    where fetched executables are loaded (default: the first local
    device — the warmup's publish-then-verify round trip)."""
    root = getattr(cfg.serve, "artifacts_dir", "")
    if not root:
        return None
    import jax
    return ArtifactStore(os.path.abspath(os.path.expanduser(root)),
                         backend=jax.default_backend(), device=device)
