"""Executable artifact plane tests (DESIGN.md "Artifact plane",
serve/artifacts.py).

Unit tier: store publish/fetch round-trip with bitwise output parity,
atomic first-writer-wins publish, the integrity gates (tampered
manifest, tampered blob, drifted code, backend/version skew — every one
refuses to load, falls back to compile, and counts), the stdlib-only
verify/gc half, the jax-free `deepof_tpu artifacts` CLI verb's rc
contract (0 ok / 1 corrupt / 2 empty — verify-ckpt's convention), and
ledger_diff treating an artifact load as a non-recompile.

Slow tier: `warmup --serve` publishes the bucket x tier ladder and a
cold engine boots with ONLY artifact_hit rows, its flows bitwise equal
to the compile-path engine's on identical requests.

Chaos tier (slow, subprocess): a REAL-model fleet with the store on —
SIGKILL the scale-up replica mid-boot, the supervisor respawns it, every
request resolves via failover, and the respawned replica's ledger shows
it booted from artifacts (zero "aot" rows fleet-wide).

r17 executable index tier: the trace-free resolution plane — pure key
algebra (resolution_key / serve_config_digest / params_aval_sig),
atomic index publish + tolerant load, the resolve() trust gates (forged
entry, stale target, cross-wired name, version skew, tampered payload —
every one a loud counted reject), roots-pinned GC with index pruning,
supervisor GC wiring, the index-boot engine (only index_hit rows — zero
trace/lower on the resolve path), config-drift miss + fallback, the
deep-verify demote drill, and `artifacts verify --deep`'s rc contract.
"""

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
import zlib

import numpy as np
import pytest

from deepof_tpu.serve.artifacts import (BLOB, INDEX, MANIFEST, gc_store,
                                        index_targets, load_index,
                                        resolution_key,
                                        serve_config_digest, store_entries,
                                        verify_entry, verify_store,
                                        write_index)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def no_persistent_compile_cache():
    """What these tests publish must come from a real compile. On the CPU
    backend (jax 0.9) an executable that was LOADED from the persistent
    compile cache re-serializes into a payload whose functions are gone
    ("Function ... not found" at its first call), so with the suite's
    warm cache the round trips below failed on every run but the first."""
    import jax

    from deepof_tpu.train import warmup

    prev = jax.config.jax_compilation_cache_dir
    warmup.disable_compile_cache()
    yield
    if prev is not None:
        warmup.enable_compile_cache(prev)


# ----------------------------------------------------------- helpers


def _store(tmp_path, backend="cpu"):
    from deepof_tpu.serve.artifacts import ArtifactStore

    return ArtifactStore(str(tmp_path / "exec"), backend=backend)


def _ledger(tmp_path, name="run"):
    from deepof_tpu.obs.ledger import ExecutableLedger

    return ExecutableLedger(str(tmp_path / name), backend="cpu")


def _tiny_lower():
    """A lowering cheap enough for the unit tier: elementwise jit."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x, y: (x @ y + 1.0, y * 2.0))
    a = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    return lambda: f.lower(a, a)


def _fake_entry(root: str, fp: str, payload: bytes = b"x" * 64,
                **manifest_overrides) -> None:
    """A hand-built store entry (stdlib only — no jax) whose manifest is
    self-consistent unless an override breaks it on purpose."""
    d = os.path.join(root, fp)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, BLOB), "wb") as f:
        f.write(payload)
    man = {"schema": 1, "fingerprint": fp, "name": "fake",
           "backend": "cpu", "jax": "0.0.0", "compile_s": 0.1,
           "created": 123.0,
           "payload": {"file": BLOB, "size": len(payload),
                       "crc32": zlib.crc32(payload) & 0xFFFFFFFF}}
    man.update(manifest_overrides)
    with open(os.path.join(d, MANIFEST), "w") as f:
        json.dump(man, f)


# ------------------------------------------------------ stdlib half


def test_verify_store_and_gc_stdlib_only(tmp_path):
    """The jax-free half the CLI verb rides: structural verification
    (schema, fingerprint-vs-dirname, payload size, crc32) and gc of
    corrupt + abandoned-tmp entries, valid ones kept."""
    root = str(tmp_path / "exec")
    _fake_entry(root, "a" * 16)
    _fake_entry(root, "b" * 16)
    os.makedirs(os.path.join(root, ".tmp-999-deadbeef"))
    # corrupt b: flip payload bytes without updating the manifest crc
    with open(os.path.join(root, "b" * 16, BLOB), "wb") as f:
        f.write(b"y" * 64)

    rep = verify_store(root)
    assert rep["total"] == 2 and rep["ok"] == 1
    assert rep["corrupt"] == ["b" * 16]
    assert rep["tmp_dirs"] == [".tmp-999-deadbeef"]
    good = verify_entry(root, "a" * 16)
    assert good["ok"] and good["name"] == "fake" and good["size"] == 64

    gc = gc_store(root)
    assert gc["removed"] == ["b" * 16]
    assert gc["kept"] == ["a" * 16]
    assert gc["tmp_removed"] == [".tmp-999-deadbeef"]
    assert store_entries(root) == ["a" * 16]


def test_verify_entry_catches_fingerprint_dirname_mismatch(tmp_path):
    """A manifest whose fingerprint disagrees with its directory name is
    corrupt — a renamed/copied entry must never verify."""
    root = str(tmp_path / "exec")
    _fake_entry(root, "c" * 16, fingerprint="d" * 16)
    ent = verify_entry(root, "c" * 16)
    assert not ent["ok"] and "fingerprint" in ent["why"]
    assert verify_store(root)["corrupt"] == ["c" * 16]


def test_gc_older_than_keeps_fresh_valid_entries(tmp_path):
    root = str(tmp_path / "exec")
    _fake_entry(root, "e" * 16, created=time.time())
    _fake_entry(root, "f" * 16, created=time.time() - 40 * 86400)
    gc = gc_store(root, older_than_days=30)
    assert gc["removed"] == ["f" * 16]
    assert gc["kept"] == ["e" * 16]


# ------------------------------------------------------- cli verb


def _cli(args, timeout=60):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-m", "deepof_tpu", "artifacts",
                           *args], capture_output=True, text=True, env=env,
                          timeout=timeout)


def test_cli_artifacts_rc_contract(tmp_path):
    """`deepof_tpu artifacts` mirrors verify-ckpt's rc ladder: 2 on an
    empty store, 1 when any entry is corrupt, 0 when all verify; gc
    reports what it removed and exits 0. The verb is jax-free — it must
    answer fast even where jax can't import."""
    root = str(tmp_path / "exec")
    os.makedirs(root)
    r = _cli(["list", "--dir", root])
    assert r.returncode == 2 and "empty store" in r.stderr

    _fake_entry(root, "a" * 16)
    r = _cli(["verify", "--dir", root])
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout)
    assert rep["total"] == 1 and rep["ok"] == 1 and not rep["corrupt"]

    with open(os.path.join(root, "a" * 16, BLOB), "ab") as f:
        f.write(b"junk")
    r = _cli(["verify", "--dir", root])
    assert r.returncode == 1
    assert json.loads(r.stdout)["corrupt"] == ["a" * 16]

    r = _cli(["gc", "--dir", root])
    assert r.returncode == 0
    assert json.loads(r.stdout)["removed"] == ["a" * 16]
    r = _cli(["list", "--dir", root])
    assert r.returncode == 2


# ------------------------------------------------- store round-trip


def test_publish_fetch_roundtrip_bitwise_parity(tmp_path):
    """The tentpole's core loop: record_aot publishes nothing itself —
    the store's publish/fetch round-trips a serialized executable whose
    outputs are BITWISE equal to the in-process compile's, the hit is
    ledgered as compile_kind="artifact" + cache_verdict="artifact_hit",
    and the artifact row's resolve_s (fetch+deserialize) is what the
    acquisition figures are built from."""
    from deepof_tpu.obs.ledger import ROW_KEYS

    store = _store(tmp_path)
    lower = _tiny_lower()
    led = _ledger(tmp_path, "a")
    compiled, row = led.record_aot("demo", lower, artifacts=store)
    assert row["compile_kind"] == "aot"
    assert tuple(row.keys()) == ROW_KEYS
    assert store.publish(row["fingerprint"], compiled,
                         name="demo") == "published"
    # first-writer-wins: a second publish is a no-op, not a corruption
    assert store.publish(row["fingerprint"], compiled) == "exists"

    led2 = _ledger(tmp_path, "b")
    c2, row2 = led2.record_aot("demo", lower, artifacts=store)
    assert row2["compile_kind"] == "artifact"
    assert row2["cache_verdict"] == "artifact_hit"
    assert row2["resolve_s"] is not None
    st = led2.stats()
    assert st["exec_artifact_hits"] == 1
    assert st["exec_artifact_misses"] == 0

    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    y = np.random.RandomState(1).randn(8, 8).astype(np.float32)
    for a, b in zip(compiled(x, y), c2(x, y)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_tampered_blob_and_manifest_refuse_to_load(tmp_path, capsys):
    """Both tamper axes: a crc-broken blob and a fingerprint-forged
    manifest each REJECT (loud stderr warn), fall back to compile, and
    count in exec_artifact_rejects — a stale artifact can never load."""
    store = _store(tmp_path)
    lower = _tiny_lower()
    led = _ledger(tmp_path, "a")
    compiled, row = led.record_aot("demo", lower, artifacts=store)
    fp = row["fingerprint"]
    store.publish(fp, compiled)

    blob = os.path.join(store.root, fp, BLOB)
    data = open(blob, "rb").read()
    with open(blob, "wb") as f:
        f.write(data[:-4] + b"XXXX")
    led2 = _ledger(tmp_path, "b")
    c2, row2 = led2.record_aot("demo", lower, artifacts=store)
    assert row2["compile_kind"] == "aot"  # fell back to compile
    assert led2.stats()["exec_artifact_rejects"] == 1
    assert "REJECT" in capsys.readouterr().err
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    assert np.isfinite(np.asarray(c2(x, x)[0])).all()  # run completes

    with open(blob, "wb") as f:
        f.write(data)  # restore the blob, forge the manifest instead
    man_path = os.path.join(store.root, fp, MANIFEST)
    man = json.load(open(man_path))
    man["fingerprint"] = "0" * 16
    with open(man_path, "w") as f:
        json.dump(man, f)
    led3 = _ledger(tmp_path, "c")
    _, row3 = led3.record_aot("demo", lower, artifacts=store)
    assert row3["compile_kind"] == "aot"
    assert led3.stats()["exec_artifact_rejects"] == 1


def test_drifted_code_misses_and_falls_back(tmp_path):
    """The integrity gate is the fingerprint recomputed from the LOCAL
    lowering: code drift changes the fingerprint, so the stale artifact
    is simply never looked up — a miss, a compile, a counted fallback."""
    import jax
    import jax.numpy as jnp

    store = _store(tmp_path)
    led = _ledger(tmp_path, "a")
    compiled, row = led.record_aot("demo", _tiny_lower(), artifacts=store)
    store.publish(row["fingerprint"], compiled)

    drifted = jax.jit(lambda x, y: (x @ y + 2.0, y * 2.0))  # the "edit"
    a = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    led2 = _ledger(tmp_path, "b")
    _, row2 = led2.record_aot("demo", lambda: drifted.lower(a, a),
                              artifacts=store)
    assert row2["compile_kind"] == "aot"
    assert row2["fingerprint"] != row["fingerprint"]
    assert led2.stats()["exec_artifact_misses"] == 1
    assert led2.stats()["exec_artifact_hits"] == 0


def test_backend_skew_rejects(tmp_path):
    """An artifact serialized for another backend must refuse to load
    even when the fingerprint matches (the StableHLO is backend-neutral;
    the serialized executable is NOT)."""
    store = _store(tmp_path)
    led = _ledger(tmp_path, "a")
    compiled, row = led.record_aot("demo", _tiny_lower(), artifacts=store)
    fp = row["fingerprint"]
    store.publish(fp, compiled)
    man_path = os.path.join(store.root, fp, MANIFEST)
    man = json.load(open(man_path))
    man["backend"] = "tpu"
    with open(man_path, "w") as f:
        json.dump(man, f)
    got, verdict = store.fetch(fp)
    assert got is None and verdict.startswith("reject:")


def test_store_for_config_resolves_path_and_off_switch(tmp_path):
    """serve.artifacts_dir="" keeps the plane off (None store — the
    pre-r16 behavior byte-identical); a relative path resolves to an
    absolute root so replica cwd never decides which store boots."""
    from deepof_tpu.core.config import get_config
    from deepof_tpu.serve.artifacts import store_for_config

    cfg = get_config("flyingchairs")
    assert store_for_config(cfg) is None
    cwd = os.getcwd()
    try:
        os.chdir(tmp_path)
        cfg2 = cfg.replace(serve=dataclasses.replace(
            cfg.serve, artifacts_dir="rel/exec"))
        store = store_for_config(cfg2)
        assert os.path.isabs(store.root)
        assert store.root == os.path.join(str(tmp_path), "rel", "exec")
    finally:
        os.chdir(cwd)


# -------------------------------------------------- ledger provenance


def test_ledger_diff_artifact_load_is_not_a_recompile(tmp_path):
    """The r15 sentinel must not rc-8 a replica that booted from the
    store: the baseline's cache-hit row vs a live artifact row (zero
    persistent-cache activity) is a FETCH, not a recompile."""
    from deepof_tpu.obs.ledger import diff_ledgers, lowering_row

    base = lowering_row("serve_64x64_f32", compile_s=1.0,
                        compile_kind="aot",
                        cache={"requests": 1, "hits": 1, "misses": 0})
    live = lowering_row("serve_64x64_f32", compile_s=0.01,
                        compile_kind="artifact",
                        cache={"requests": 1, "hits": 0, "misses": 1},
                        cache_verdict="artifact_hit")
    rep = diff_ledgers([base], [live])
    assert rep["unexpected_recompiles"] == []
    assert not rep["failed"], rep

    # control: the same cache shape WITHOUT the artifact kind still
    # trips the sentinel — the guard is the kind, not a blanket skip
    live_miss = lowering_row("serve_64x64_f32", compile_s=1.0,
                             compile_kind="aot",
                             cache={"requests": 1, "hits": 0, "misses": 1})
    rep2 = diff_ledgers([base], [live_miss])
    assert rep2["unexpected_recompiles"], rep2


# --------------------------------------------------- slow: full ladder


@pytest.mark.slow
def test_warmup_publishes_ladder_then_cold_engine_boots_from_store(
        tmp_path):
    """The r16 acceptance, in-process: `warmup --serve` publishes the
    full bucket x tier ladder into the store (single writer), a cold
    engine (cleared jax caches, index OFF — the fingerprint boot path
    kept for continuity; the r17 index path has its own test below)
    warms with ONLY artifact hits — zero compiles — and serves flows
    BITWISE equal to a compile-path engine's on identical requests at
    the same bucket/tier."""
    import jax
    import jax.numpy as jnp

    from deepof_tpu.core.config import get_config
    from deepof_tpu.serve.engine import InferenceEngine, build_serve_model
    from deepof_tpu.train import warmup

    buckets = ((32, 64),)
    tiers = ("f32", "bf16")
    cfg = get_config("flyingchairs")
    cfg = cfg.replace(
        model="flownet_s", width_mult=0.25,
        data=dataclasses.replace(cfg.data, dataset="synthetic",
                                 image_size=(32, 64), gt_size=(32, 64)),
        serve=dataclasses.replace(cfg.serve, max_batch=2,
                                  batch_timeout_ms=40.0, buckets=buckets,
                                  precisions=tiers,
                                  artifacts_dir=str(tmp_path / "exec")),
        train=dataclasses.replace(cfg.train, eval_amplifier=1.0,
                                  eval_clip=(-1e6, 1e6),
                                  log_dir=str(tmp_path / "run")))

    rep = warmup.warmup_serve(cfg)
    ladder = len(buckets) * len(tiers)
    assert rep["artifacts"]["published"] == ladder
    assert rep["artifacts"]["errors"] == 0
    assert all(b["artifact"] == "published" for b in rep["buckets"])
    assert verify_store(str(tmp_path / "exec"))["ok"] == ladder

    model = build_serve_model(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 32, 64, 6)))["params"]
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(1, 255, (30, 60, 3), dtype=np.uint8),
             rng.randint(1, 255, (30, 60, 3), dtype=np.uint8), t)
            for t in tiers]

    jax.clear_caches()  # the cold scaled-up replica (fingerprint path)
    cfg_fp = cfg.replace(serve=dataclasses.replace(cfg.serve,
                                                   artifacts_index=False))
    with InferenceEngine(cfg_fp, model_params=(model, params)) as eng:
        eng.warm()
        st = eng.stats()
        assert st["exec_artifact_hits"] >= ladder, st
        assert st["exec_artifact_misses"] == 0, st
        assert st["exec_artifact_rejects"] == 0, st
        flows_art = [eng.submit(p, n, precision=t).result(timeout=300)
                     ["flow"] for p, n, t in reqs]
    # ledger provenance: the cold boot wrote ONLY artifact rows
    kinds = [json.loads(line).get("compile_kind")
             for line in open(tmp_path / "run" / "ledger.jsonl")]
    assert kinds.count("artifact") >= ladder
    # the publish pass wrote the "aot" rows; the cold boot none
    assert kinds.count("aot") == ladder

    jax.clear_caches()  # the compile-path control engine
    cfg_off = cfg.replace(serve=dataclasses.replace(cfg.serve,
                                                    artifacts_dir=""))
    with InferenceEngine(cfg_off, model_params=(model, params)) as eng:
        eng.warm()
        flows_cmp = [eng.submit(p, n, precision=t).result(timeout=300)
                     ["flow"] for p, n, t in reqs]
    for fa, fc in zip(flows_art, flows_cmp):
        assert fa.dtype == fc.dtype
        assert (fa == fc).all(), "artifact executable diverged bitwise"


# --------------------------------------------- r17: executable index


def _index_entry(name, fp, backend="cpu", jax_version=None,
                 config_digest="d" * 16, aval_sig="s" * 16, **overrides):
    """A well-formed index entry plus its honest resolution key."""
    if jax_version is None:
        import jax

        jax_version = jax.__version__
    ent = {"name": name, "fingerprint": fp,
           "config_digest": config_digest, "aval_sig": aval_sig,
           "backend": backend, "jax": jax_version, "created": 123.0}
    ent.update(overrides)
    key = resolution_key(ent["name"], ent["config_digest"],
                         ent["aval_sig"], ent["backend"], ent["jax"])
    return key, ent


def test_resolution_key_and_config_digest_are_pure():
    """jax-free key algebra: deterministic, sensitive to every
    component; the config digest covers exactly the lowering-relevant
    subset — replica plumbing (ports, log dirs, store paths) must NOT
    flip it, while anything that shapes the lattice must."""
    k = resolution_key("n", "d" * 16, "s" * 16, "cpu", "1.0")
    assert k == resolution_key("n", "d" * 16, "s" * 16, "cpu", "1.0")
    assert len(k) == 16 and all(c in "0123456789abcdef" for c in k)
    others = [resolution_key("m", "d" * 16, "s" * 16, "cpu", "1.0"),
              resolution_key("n", "e" * 16, "s" * 16, "cpu", "1.0"),
              resolution_key("n", "d" * 16, "t" * 16, "cpu", "1.0"),
              resolution_key("n", "d" * 16, "s" * 16, "tpu", "1.0"),
              resolution_key("n", "d" * 16, "s" * 16, "cpu", "2.0")]
    assert len({k, *others}) == 6

    from deepof_tpu.core.config import get_config

    cfg = get_config("flyingchairs")
    base = serve_config_digest(cfg)
    runtime = cfg.replace(
        train=dataclasses.replace(cfg.train, log_dir="/elsewhere"),
        serve=dataclasses.replace(
            cfg.serve, port=9999, artifacts_dir="/some/store",
            fleet=dataclasses.replace(cfg.serve.fleet, replicas=7)))
    assert serve_config_digest(runtime) == base
    assert serve_config_digest(cfg.replace(width_mult=0.5)) != base
    assert serve_config_digest(cfg.replace(serve=dataclasses.replace(
        cfg.serve, max_batch=cfg.serve.max_batch + 1))) != base


def test_index_write_is_atomic_merge_and_load_is_tolerant(tmp_path):
    """write_index merges over the existing index through a tmp-file +
    rename (no torn reader window, no staging left behind); load_index
    treats an absent/torn/wrong-schema index as EMPTY — on the boot
    path that is a miss, never an exception."""
    root = str(tmp_path / "exec")
    k1, e1 = _index_entry("a", "1" * 16)
    write_index(root, {k1: e1})
    k2, e2 = _index_entry("b", "2" * 16)
    idx = write_index(root, {k2: e2})
    assert set(idx["entries"]) == {k1, k2}  # merge, not replace
    assert load_index(root)["entries"][k1]["fingerprint"] == "1" * 16
    assert index_targets(root) == {"1" * 16, "2" * 16}
    assert not [n for n in os.listdir(root) if n.startswith(".tmp-")]

    with open(os.path.join(root, INDEX), "w") as f:
        f.write('{"schema": 1, "entries": {"x": ')  # torn mid-write
    assert load_index(root)["entries"] == {}
    with open(os.path.join(root, INDEX), "w") as f:
        json.dump({"schema": 99, "entries": {}}, f)
    assert load_index(root)["entries"] == {}
    assert index_targets(os.path.join(root, "missing")) == set()


def test_index_resolve_roundtrip_counts_and_row(tmp_path):
    """record_index: an honest entry resolves trace-free (fetch +
    deserialize only), writes the cache_verdict="index_hit" row
    carrying the INDEX's fingerprint, queues one deep-verify slot, and
    the resolved executable's outputs are bitwise equal to the
    compile-path one's. A drifted config is a DIFFERENT key: a clean
    counted miss, no row."""
    store = _store(tmp_path)
    led = _ledger(tmp_path, "a")
    compiled, row = led.record_aot("demo", _tiny_lower(), artifacts=store)
    store.publish(row["fingerprint"], compiled, name="demo")
    key, ent = _index_entry("demo", row["fingerprint"])
    write_index(store.root, {key: ent})

    led2 = _ledger(tmp_path, "b")
    c2, row2, verdict = led2.record_index("demo", _store(tmp_path), key)
    assert verdict == "index_hit"
    assert row2["compile_kind"] == "artifact"
    assert row2["cache_verdict"] == "index_hit"
    assert row2["fingerprint"] == row["fingerprint"]
    assert row2["resolve_s"] is not None
    st = led2.stats()
    assert st["exec_index_hits"] == 1 and st["exec_index_misses"] == 0
    assert st["exec_index_rejects"] == 0
    assert st["exec_deep_verify_pending"] == 1
    led2.note_deep_verify(True)
    st = led2.stats()
    assert st["exec_deep_verify_pending"] == 0
    assert st["exec_deep_verify_ok"] == 1

    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    y = np.random.RandomState(1).randn(8, 8).astype(np.float32)
    for a, b in zip(compiled(x, y), c2(x, y)):
        assert (np.asarray(a) == np.asarray(b)).all()

    k_drift, _ = _index_entry("demo", row["fingerprint"],
                              config_digest="f" * 16)
    c3, row3, verdict3 = led2.record_index("demo", _store(tmp_path),
                                           k_drift)
    assert (c3, row3, verdict3) == (None, None, "index_miss")
    assert led2.stats()["exec_index_misses"] == 1


def test_index_trust_gates_reject_loudly(tmp_path, capsys):
    """Every poisoned-index case REFUSES to serve, warns on stderr, and
    counts in exec_index_rejects: a forged entry (components do not
    hash back to the key), a stale target (entry outlived its
    executable), a cross-wired target (manifest name disagrees), a
    version-skewed entry, and a tampered payload behind an honest
    entry. None of them raises — the caller falls back to the lowering
    path."""
    store = _store(tmp_path)
    led = _ledger(tmp_path, "a")
    compiled, row = led.record_aot("demo", _tiny_lower(), artifacts=store)
    fp = row["fingerprint"]
    store.publish(fp, compiled, name="demo")
    led2 = _ledger(tmp_path, "b")

    def resolve(key):
        return led2.record_index("demo", _store(tmp_path), key)[2]

    # forged: key hashed over name "demo", entry claims another name
    key, ent = _index_entry("demo", fp)
    write_index(store.root, {key: dict(ent, name="other")})
    assert resolve(key) == "index_reject:entry_forged"

    # stale target: honest entry, executable no longer in the store
    k2, e2 = _index_entry("demo", "0" * 16)
    write_index(store.root, {k2: e2})
    assert resolve(k2) == "index_reject:stale_target"

    # cross-wired: honest entry under another name pointing at demo's
    # artifact — the target manifest's recorded name disagrees
    k3, e3 = _index_entry("other", fp)
    write_index(store.root, {k3: e3})
    assert resolve(k3) == "index_reject:name_mismatch"

    # version skew: entry lowered under another jax
    k4, e4 = _index_entry("demo", fp, jax_version="0.0.0")
    write_index(store.root, {k4: e4})
    assert resolve(k4) == "index_reject:jax_version_mismatch"

    # tampered payload behind an honest entry: the fetch gates fire
    write_index(store.root, {key: ent})
    blob = os.path.join(store.root, fp, BLOB)
    data = open(blob, "rb").read()
    with open(blob, "wb") as f:
        f.write(data[:-4] + b"XXXX")
    assert resolve(key).startswith("index_reject:target_")

    st = led2.stats()
    assert st["exec_index_rejects"] == 5
    assert st["exec_index_hits"] == 0
    assert "INDEX REJECT" in capsys.readouterr().err


def test_gc_pins_roots_and_index_targets_and_prunes_stale(tmp_path):
    """Retirement-path GC safety: live-lattice roots and the index's
    own targets are pinned against the age sweep; a corrupt entry goes
    regardless and its index entries are PRUNED (a later boot takes a
    clean miss, not a stale-target reject); leftover `.tmp-*-index.json`
    staging FILES are swept like tmp dirs."""
    root = str(tmp_path / "exec")
    old = time.time() - 40 * 86400
    _fake_entry(root, "a" * 16, created=old)  # pinned via roots
    _fake_entry(root, "b" * 16, created=old)  # pinned via the index
    _fake_entry(root, "c" * 16, created=old)  # unpinned: swept by age
    _fake_entry(root, "e" * 16, created=old)  # corrupt: goes regardless
    with open(os.path.join(root, "e" * 16, BLOB), "wb") as f:
        f.write(b"tampered" * 8)
    kb, eb = _index_entry("fake", "b" * 16)
    ke, ee = _index_entry("fake2", "e" * 16, aval_sig="t" * 16)
    write_index(root, {kb: eb, ke: ee})
    with open(os.path.join(root, ".tmp-42-index.json"), "w") as f:
        f.write("{}")

    gc = gc_store(root, older_than_days=30, roots={"a" * 16})
    assert sorted(gc["removed"]) == ["c" * 16, "e" * 16]
    assert sorted(gc["kept"]) == ["a" * 16, "b" * 16]
    assert ".tmp-42-index.json" in gc["tmp_removed"]
    assert gc["index_pruned"] == [ke]
    assert set(load_index(root)["entries"]) == {kb}
    assert not os.path.exists(os.path.join(root, ".tmp-42-index.json"))


def test_fleet_retirement_gc_wiring(tmp_path):
    """Satellite 1: the supervisor's retirement hook sweeps the store
    with every replica ledger's fingerprints as roots (index targets
    pinned inside gc_store) and logs one warn record into the fleet's
    metrics.jsonl — exercised directly, no processes spawned."""
    from deepof_tpu.core.config import get_config
    from deepof_tpu.serve.fleet import Fleet

    store_root = str(tmp_path / "exec")
    old = time.time() - 40 * 86400
    _fake_entry(store_root, "a" * 16, created=old)  # a live ledger's fp
    _fake_entry(store_root, "b" * 16, created=old)  # unpinned: swept
    fleet_dir = str(tmp_path / "fleet")
    rdir = os.path.join(fleet_dir, "replica-0")
    os.makedirs(rdir)
    with open(os.path.join(rdir, "ledger.jsonl"), "w") as f:
        f.write(json.dumps({"kind": "lowering", "name": "x",
                            "fingerprint": "a" * 16}) + "\n")

    cfg = get_config("flyingchairs")
    cfg = cfg.replace(
        serve=dataclasses.replace(
            cfg.serve, artifacts_dir=store_root,
            fleet=dataclasses.replace(cfg.serve.fleet,
                                      artifacts_gc_days=30.0)),
        train=dataclasses.replace(cfg.train, log_dir=fleet_dir))
    fleet = Fleet(cfg, 1)
    fleet._artifacts_gc("test")
    assert store_entries(store_root) == ["a" * 16]
    recs = [json.loads(line)
            for line in open(os.path.join(fleet_dir, "metrics.jsonl"))]
    assert any("artifacts gc" in r.get("message", "") for r in recs)


@pytest.mark.slow
def test_index_boot_is_trace_free_and_bitwise_equal(tmp_path):
    """The r17 tentpole acceptance, in-process: `warmup --serve` writes
    the executable index, a cold engine resolves the WHOLE ladder
    through it — ledger provenance shows ONLY index_hit rows on the
    resolve path (zero "aot", zero untagged lowerings; deep-verify rows
    are the off-path integrity plane, which confirms every entry) —
    and serves flows bitwise equal to the compile-path engine's. A
    config drift (different lowering-relevant subset) flips the
    resolution key: the index MISSES and the engine falls back to the
    compile path, loudly counted."""
    import jax
    import jax.numpy as jnp

    from deepof_tpu.core.config import get_config
    from deepof_tpu.serve.engine import InferenceEngine, build_serve_model
    from deepof_tpu.train import warmup

    tiers = ("f32", "bf16")
    cfg = get_config("flyingchairs")
    cfg = cfg.replace(
        model="flownet_s", width_mult=0.25,
        data=dataclasses.replace(cfg.data, dataset="synthetic",
                                 image_size=(32, 64), gt_size=(32, 64)),
        serve=dataclasses.replace(cfg.serve, max_batch=2,
                                  batch_timeout_ms=40.0,
                                  buckets=((32, 64),), precisions=tiers,
                                  artifacts_dir=str(tmp_path / "exec")),
        train=dataclasses.replace(cfg.train, eval_amplifier=1.0,
                                  eval_clip=(-1e6, 1e6),
                                  log_dir=str(tmp_path / "publish")))
    rep = warmup.warmup_serve(cfg)
    ladder = len(rep["buckets"])
    assert rep["artifacts"]["index_entries"] == ladder

    model = build_serve_model(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 32, 64, 6)))["params"]
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(1, 255, (30, 60, 3), dtype=np.uint8),
             rng.randint(1, 255, (30, 60, 3), dtype=np.uint8), t)
            for t in tiers]

    jax.clear_caches()  # the cold scaled-up replica (index path)
    cfg_cold = cfg.replace(train=dataclasses.replace(
        cfg.train, log_dir=str(tmp_path / "cold")))
    with InferenceEngine(cfg_cold, model_params=(model, params)) as eng:
        eng.warm()
        st = eng.stats()
        assert st["exec_index_hits"] >= ladder, st
        assert st["exec_index_misses"] == 0, st
        assert st["exec_index_rejects"] == 0, st
        # resolution never even reached the fingerprint path
        assert st["exec_artifact_hits"] == 0, st
        flows_idx = [eng.submit(p, n, precision=t).result(timeout=300)
                     ["flow"] for p, n, t in reqs]
        assert eng.deep_verify_join(timeout_s=300)
        st = eng.stats()
        assert st["exec_deep_verify_ok"] >= ladder, st
        assert st["exec_deep_verify_demoted"] == 0, st
        assert st["exec_deep_verify_pending"] == 0, st
    rows = [json.loads(line)
            for line in open(tmp_path / "cold" / "ledger.jsonl")]
    kinds = [r.get("compile_kind") for r in rows]
    assert kinds.count("artifact") >= ladder
    for r in rows:
        assert r.get("compile_kind") in (None, "artifact",
                                         "deep_verify"), r
        if r.get("compile_kind") == "artifact":
            assert r.get("cache_verdict") == "index_hit", r

    jax.clear_caches()  # the compile-path control engine
    cfg_off = cfg.replace(
        serve=dataclasses.replace(cfg.serve, artifacts_dir=""),
        train=dataclasses.replace(cfg.train,
                                  log_dir=str(tmp_path / "control")))
    with InferenceEngine(cfg_off, model_params=(model, params)) as eng:
        eng.warm()
        flows_cmp = [eng.submit(p, n, precision=t).result(timeout=300)
                     ["flow"] for p, n, t in reqs]
    for fa, fc in zip(flows_idx, flows_cmp):
        assert fa.dtype == fc.dtype
        assert (fa == fc).all(), "index executable diverged bitwise"

    # config drift: a bigger max_batch lowers different avals — the
    # key changes, the index misses, the compile path takes over
    jax.clear_caches()
    cfg_drift = cfg.replace(
        serve=dataclasses.replace(cfg.serve, max_batch=3),
        train=dataclasses.replace(cfg.train,
                                  log_dir=str(tmp_path / "drift")))
    with InferenceEngine(cfg_drift, model_params=(model, params)) as eng:
        eng.warm()
        st = eng.stats()
        assert st["exec_index_misses"] >= ladder, st
        assert st["exec_index_hits"] == 0, st
    kinds = [json.loads(line).get("compile_kind")
             for line in open(tmp_path / "drift" / "ledger.jsonl")]
    assert kinds.count("aot") >= ladder  # loud fallback, not silence


@pytest.mark.slow
def test_deep_verify_demotes_cross_wired_index_entry(tmp_path):
    """The deferred integrity plane: cross-wire the f32 cold entry to
    the bf16 tier's artifact with the target manifest's name forged to
    match — every boot-path gate passes, so the engine serves the
    stale index hit. The background deep verify re-lowers, sees the
    fingerprint mismatch, DEMOTES loudly (counter + ledger row) and
    swaps in a fresh compile; requests after the swap produce flows
    bitwise equal to the compile path's."""
    import jax
    import jax.numpy as jnp

    from deepof_tpu.core.config import get_config
    from deepof_tpu.serve.engine import InferenceEngine, build_serve_model
    from deepof_tpu.train import warmup

    store_root = str(tmp_path / "exec")
    cfg = get_config("flyingchairs")
    cfg = cfg.replace(
        model="flownet_s", width_mult=0.25,
        data=dataclasses.replace(cfg.data, dataset="synthetic",
                                 image_size=(32, 64), gt_size=(32, 64)),
        serve=dataclasses.replace(cfg.serve, max_batch=2,
                                  batch_timeout_ms=40.0,
                                  buckets=((32, 64),),
                                  precisions=("f32", "bf16"),
                                  artifacts_dir=store_root),
        train=dataclasses.replace(cfg.train, eval_amplifier=1.0,
                                  eval_clip=(-1e6, 1e6),
                                  log_dir=str(tmp_path / "publish")))
    warmup.warmup_serve(cfg)

    # the poisoning: f32's entry now claims bf16's artifact, and the
    # target manifest is forged to agree on the name
    idx = load_index(store_root)
    by_name = {e["name"]: (k, e) for k, e in idx["entries"].items()}
    (k_f32, e_f32), = [v for n, v in by_name.items()
                       if n.endswith(":f32:cold")]
    (_, e_bf16), = [v for n, v in by_name.items()
                    if n.endswith(":bf16:cold")]
    victim_fp = e_bf16["fingerprint"]
    assert victim_fp != e_f32["fingerprint"]
    write_index(store_root, {k_f32: dict(e_f32, fingerprint=victim_fp)})
    man_path = os.path.join(store_root, victim_fp, MANIFEST)
    man = json.load(open(man_path))
    man["name"] = e_f32["name"]
    with open(man_path, "w") as f:
        json.dump(man, f)

    model = build_serve_model(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 32, 64, 6)))["params"]
    jax.clear_caches()
    cfg_cold = cfg.replace(train=dataclasses.replace(
        cfg.train, log_dir=str(tmp_path / "cold")))
    with InferenceEngine(cfg_cold, model_params=(model, params)) as eng:
        eng.warm()
        st = eng.stats()
        assert st["exec_index_hits"] >= 1, st  # the poisoned hit served
        assert eng.deep_verify_join(timeout_s=300)
        st = eng.stats()
        assert st["exec_deep_verify_demoted"] == 1, st
        assert st["exec_deep_verify_pending"] == 0, st
        # after the swap: a real f32 request through the replacement
        rng = np.random.RandomState(0)
        prev = rng.randint(1, 255, (30, 60, 3), dtype=np.uint8)
        nxt = rng.randint(1, 255, (30, 60, 3), dtype=np.uint8)
        flow = eng.submit(prev, nxt, precision="f32").result(
            timeout=300)["flow"]
    rows = [json.loads(line)
            for line in open(tmp_path / "cold" / "ledger.jsonl")]
    assert any(r.get("cache_verdict") == "deep_verify_demoted"
               for r in rows), [r.get("cache_verdict") for r in rows]

    jax.clear_caches()  # compile-path control for bitwise equality
    cfg_off = cfg.replace(
        serve=dataclasses.replace(cfg.serve, artifacts_dir=""),
        train=dataclasses.replace(cfg.train,
                                  log_dir=str(tmp_path / "control")))
    with InferenceEngine(cfg_off, model_params=(model, params)) as eng:
        flow_cmp = eng.submit(prev, nxt, precision="f32").result(
            timeout=300)["flow"]
    assert flow.dtype == flow_cmp.dtype
    assert (flow == flow_cmp).all(), "demote swap-in diverged bitwise"


@pytest.mark.slow
def test_cli_artifacts_verify_deep_rc_contract(tmp_path):
    """`deepof_tpu artifacts verify --deep` re-lowers the lattice under
    the given config and compares StableHLO fingerprints against the
    index across a PROCESS boundary (fingerprints must be stable or the
    whole plane is fiction): rc 0 when every indexed entry matches,
    rc 1 on drift (tampered index fingerprint), rc 2 when nothing is
    indexed."""
    import dataclasses as dc

    from deepof_tpu.core.config import get_config
    from deepof_tpu.train import warmup

    store_root = str(tmp_path / "exec")
    cfg = get_config("flyingchairs")
    cfg = cfg.replace(
        model="flownet_s", width_mult=0.25,
        data=dc.replace(cfg.data, image_size=(32, 64), gt_size=(32, 64),
                        dataset="synthetic"),
        serve=dc.replace(cfg.serve, max_batch=2, buckets=((32, 64),),
                         precisions=("f32",), artifacts_dir=store_root),
        train=dc.replace(cfg.train, eval_amplifier=1.0,
                         eval_clip=(-1e6, 1e6),
                         log_dir=str(tmp_path / "publish")))
    warmup.warmup_serve(cfg)

    deep_args = ["verify", "--deep", "--dir", store_root,
                 "--model", "flownet_s",
                 "--set", "width_mult=0.25",
                 "--set", "data.image_size=(32,64)",
                 "--set", "data.gt_size=(32,64)",
                 "--set", "serve.max_batch=2",
                 "--set", "serve.buckets=((32,64),)",
                 "--set", "serve.precisions=('f32',)"]
    r = _cli(deep_args, timeout=300)
    assert r.returncode == 0, (r.stdout, r.stderr)
    rep = json.loads(r.stdout)
    assert rep["ok"] == rep["total"] >= 1
    assert rep["drift"] == [] and rep["unindexed"] == []

    # drift: tamper the indexed fingerprint — rc 1, the entry named
    idx = load_index(store_root)
    key, ent = next(iter(idx["entries"].items()))
    write_index(store_root, {key: dict(ent, fingerprint="9" * 16)})
    r = _cli(deep_args, timeout=300)
    assert r.returncode == 1, (r.stdout, r.stderr)
    rep = json.loads(r.stdout)
    assert rep["drift"] == [ent["name"]]

    # empty: no index at all — rc 2
    os.remove(os.path.join(store_root, INDEX))
    r = _cli(deep_args, timeout=300)
    assert r.returncode == 2, (r.stdout, r.stderr)


# ----------------------------------------------- slow chaos: the drill


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_chaos_scale_up_sigkill_respawns_from_artifacts(tmp_path):
    """The fleet drill with the store ON and REAL-model replicas:
    publish the ladder, checkpoint the params, start a 1-replica fleet,
    drive load, scale up, SIGKILL the new replica mid-boot. The
    supervisor respawns it, 100% of requests resolve via failover to
    the surviving replica, and the respawned replica's ledger proves it
    booted from artifacts — zero "aot" rows anywhere in the fleet."""
    import base64

    import jax
    import jax.numpy as jnp

    cv2 = pytest.importorskip("cv2")

    from deepof_tpu.core.config import get_config
    from deepof_tpu.serve.engine import build_serve_model
    from deepof_tpu.serve.fleet import Fleet
    from deepof_tpu.serve.router import Router, build_router_server
    from deepof_tpu.train import warmup
    from deepof_tpu.train.checkpoint import CheckpointManager
    from deepof_tpu.train.schedule import step_decay_schedule
    from deepof_tpu.train.state import create_train_state, make_optimizer

    fleet_dir = tmp_path / "fleet"
    store_dir = str(tmp_path / "exec")
    cfg = get_config("flyingchairs")
    cfg = cfg.replace(
        model="flownet_s", width_mult=0.25,
        data=dataclasses.replace(cfg.data, dataset="synthetic",
                                 image_size=(32, 64), gt_size=(32, 64)),
        serve=dataclasses.replace(
            cfg.serve, max_batch=2, batch_timeout_ms=20.0,
            buckets=((32, 64),), precisions=("f32",),
            fake_exec_ms=None,  # REAL replicas: the artifact plane's case
            host="127.0.0.1", port=0, artifacts_dir=store_dir,
            fleet=dataclasses.replace(
                cfg.serve.fleet, poll_s=0.2, stale_after_s=10.0,
                spawn_timeout_s=180.0, term_grace_s=1.0, backoff_s=0.2,
                backoff_max_s=1.0, healthy_after_s=60.0,
                proxy_timeout_s=30.0, max_in_flight=16,
                drain_timeout_s=2.0)),
        train=dataclasses.replace(cfg.train, eval_amplifier=1.0,
                                  eval_clip=(-1e6, 1e6),
                                  log_dir=str(fleet_dir)),
        obs=dataclasses.replace(cfg.obs, heartbeat_period_s=0.2,
                                watchdog_min_s=3600.0))

    # single-writer publish (the `warmup --serve` leg)
    pub_cfg = cfg.replace(train=dataclasses.replace(
        cfg.train, log_dir=str(tmp_path / "publish")))
    rep = warmup.warmup_serve(pub_cfg)
    assert rep["artifacts"]["published"] >= 1

    # the checkpoint every replica restores (restore_params' template)
    model = build_serve_model(cfg)
    tx = make_optimizer(cfg.optim, step_decay_schedule(cfg.optim, 1))
    for idx in range(3):  # pre-seed replica dirs with the shared ckpt
        rdir = fleet_dir / f"replica-{idx}"
        rdir.mkdir(parents=True, exist_ok=True)
        if idx == 0:
            state = create_train_state(model, jnp.zeros((1, 32, 64, 6)),
                                       tx, seed=0)
            mgr = CheckpointManager(str(rdir / "ckpt"), async_save=False)
            mgr.save(state)
            mgr.finalize()
        else:
            os.symlink(str(fleet_dir / "replica-0" / "ckpt"),
                       str(rdir / "ckpt"))

    def _body(rng):
        imgs = []
        for _ in range(2):
            ok, buf = cv2.imencode(".png", rng.randint(
                1, 255, (30, 60, 3), dtype=np.uint8))
            assert ok
            imgs.append(base64.b64encode(buf.tobytes()).decode())
        return json.dumps({"prev": imgs[0], "next": imgs[1]}).encode()

    rng = np.random.RandomState(0)
    bodies = [_body(rng) for _ in range(4)]
    outcomes: list = []
    lock = threading.Lock()

    def _post(port, body):
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            conn.request("POST", "/v1/flow", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    with Fleet(cfg, 1) as fleet:
        fleet.start()
        fleet.wait_ready(min_ready=1, timeout_s=180)
        router = Router(cfg, fleet)
        fleet.on_retired = router.retire_slot
        httpd = build_router_server(cfg, router)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        port = httpd.server_address[1]
        stop = threading.Event()

        def _load():
            i = 0
            while not stop.is_set():
                try:
                    status, payload = _post(port, bodies[i % len(bodies)])
                except Exception as e:  # noqa: BLE001 - a drop is a bug
                    status, payload = -1, str(e).encode()
                with lock:
                    outcomes.append((status, payload))
                i += 1

        loader = threading.Thread(target=_load, daemon=True)
        loader.start()
        try:
            new_idx = fleet.scale_up()
            assert new_idx is not None
            # SIGKILL the scale-up replica mid-boot (before ready)
            deadline = time.monotonic() + 60
            killed = False
            while time.monotonic() < deadline and not killed:
                for d in fleet.describe():
                    if d["replica"] == new_idx and d["pid"]:
                        try:
                            os.kill(d["pid"], 9)
                            killed = True
                        except OSError:
                            pass
                        break
                if not killed:
                    time.sleep(0.05)
            assert killed, fleet.describe()
            # the supervisor respawns it and it reaches ready
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                if fleet.stats()["fleet_ready"] >= 2:
                    break
                time.sleep(0.2)
            stats = fleet.stats()
            assert stats["fleet_ready"] >= 2, stats
            assert stats["fleet_crashes"] + stats["fleet_respawns"] >= 1, \
                stats
            time.sleep(1.0)  # a beat of load on the respawned replica
        finally:
            stop.set()
            loader.join(timeout=30)
            router.draining = True
            httpd.shutdown()
            httpd.server_close()

    # 100% resolution: every request got a structured response (the
    # survivor absorbed the kill window via failover)
    assert outcomes
    bad = [(s, p[:120]) for s, p in outcomes if s != 200]
    assert not bad, (len(outcomes), bad[:5])

    # the respawned replica booted from the INDEX — trace-free: its
    # ledger has index_hit rows, and fleet-wide the only compile kinds
    # anywhere are "artifact" (index/fingerprint resolution) and
    # "deep_verify" (the background integrity plane, off the boot
    # path) — zero "aot" rows, zero untagged lowerings
    new_ledger = fleet_dir / f"replica-{new_idx}" / "ledger.jsonl"
    rows = [json.loads(line) for line in open(new_ledger)]
    kinds = [r.get("compile_kind") for r in rows]
    assert kinds.count("artifact") >= 1, kinds
    assert any(r.get("cache_verdict") == "index_hit" for r in rows), \
        [(r.get("compile_kind"), r.get("cache_verdict")) for r in rows]
    for rdir in sorted(fleet_dir.glob("replica-*")):
        lp = rdir / "ledger.jsonl"
        if lp.exists():
            for line in open(lp):
                k = json.loads(line).get("compile_kind")
                assert k in (None, "artifact", "deep_verify"), \
                    f"{rdir.name} compiled ({k}) instead of fetching"
