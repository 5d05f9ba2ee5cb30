"""The program's own trace marks, on the CPU: the host spans that
`KmerCounter` and `QueryService` open under `jax.profiler.trace`, and the
layer scopes that the update, query and finalize executables carry in
their HLO metadata.

The span and scope names are the ones the benchmark's reduction reads
(`bench/scopes.py`), so a rename on either side fails here.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fabsp, query
from repro.data import genome
from repro.launch.kc_serve import QueryService, StoreRegistry

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
from bench import scopes  # noqa: E402

K = 13
# parent span -> the spans opened directly inside it
NESTING = {
    "kc.update": {"kc.plan", "kc.grow", "kc.run", "kc.sync", "kc.commit"},
    "serve.flush": {"serve.coalesce", "query.pack", "query.put",
                    "query.run", "query.fetch", "serve.split"},
}
TOP_LEVEL = {"kc.update", "kc.finalize", "serve.flush"}


def _cfg(**kw):
    # 64 slots per PE is too few for the reads: the first update grows the
    # store, so a rehash round (`kc.grow`) is traced too
    return fabsp.DAKCConfig(**{"k": K, "chunk_reads": 32,
                               "store_capacity": 64, **kw})


def _reads():
    spec = genome.ReadSetSpec(genome_bases=2048, n_reads=64, read_len=60,
                              seed=3)
    return genome.sample_reads(spec)


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    """(name, start_ns, end_ns, args) of every program span of two updates,
    a finalize and one two-request flush, traced on the CPU."""
    from jax.profiler import ProfileData

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("pe",))
    sharding = NamedSharding(mesh, P("pe"))
    batches = [jax.device_put(b, sharding)
               for b in np.array_split(_reads(), 2)]
    kc = fabsp.KmerCounter(mesh, _cfg())
    registry = StoreRegistry(mesh)
    registry.register("g", kc)
    service = QueryService(registry)
    words = np.arange(1, 301, dtype=np.uint32)
    log_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(log_dir)):
        for b in batches:
            kc.update(b)
        result, _ = kc.finalize()
        jax.block_until_ready(result.unique)
        service.submit("g", words[:100])
        service.submit("g", words[100:])
        out = service.flush()
    assert all(not isinstance(a, Exception) for a in out)
    pd = ProfileData.from_file(str(sorted(log_dir.rglob("*.xplane.pb"))[-1]))
    found = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in scopes.PROGRAM_SPANS:
                    found.append((e.name, int(e.start_ns), int(e.end_ns),
                                  {k: v for k, v in e.stats}))
    return sorted(found, key=lambda s: (s[1], -s[2]))


def _parents(spans):
    """Each span's innermost enclosing program span (None at top level)."""
    out, stack = [], []
    for name, s, e, _ in spans:
        while stack and stack[-1][2] <= s:
            stack.pop()
        out.append((name, stack[-1][0] if stack else None))
        stack.append((name, s, e))
    return out


def test_every_span_the_benchmark_reads_is_emitted(spans):
    assert {n for n, *_ in spans} == set(scopes.PROGRAM_SPANS)


@pytest.mark.parametrize("name", scopes.PROGRAM_SPANS)
def test_span_nests_where_it_belongs(spans, name):
    parents = {p for n, p in _parents(spans) if n == name}
    if name in TOP_LEVEL:
        assert parents == {None}
    else:
        want = {p for p, kids in NESTING.items() if name in kids}
        assert parents == want


def test_span_args_tie_the_spans_of_one_batch_and_flush(spans):
    updates = [a for n, _, _, a in spans if n == "kc.update"]
    assert [a["batch"] for a in updates] == [0, 1]
    for n, _, _, a in spans:
        if n in ("kc.plan", "kc.grow", "kc.run", "kc.sync"):
            assert set(a) == {"batch", "round"}
    rounds = [a["round"] for n, _, _, a in spans if n == "kc.run"
              and a["batch"] == 0]
    assert rounds == list(range(len(rounds))) and len(rounds) > 1
    flush = [a for n, _, _, a in spans if n == "serve.flush"]
    assert flush == [{"requests": 2, "queries": 300}]
    run = [a for n, _, _, a in spans if n == "query.run"]
    assert run == [{"n_local": 512}]


def test_commit_span_carries_the_insert_group_counts(spans):
    commits = [a for n, _, _, a in spans if n == "kc.commit"]
    assert [a["batch"] for a in commits] == [0, 1]
    for a in commits:
        assert set(a) == {"batch", "insert_groups", "insert_fallbacks",
                          "wire_bytes", "load_max_over_mean"}
        assert 0 <= a["insert_fallbacks"] <= a["insert_groups"]
        # what the batch shipped: its padded tiles, and on one PE a load
        # of exactly the mean
        assert int(a["wire_bytes"]) > 0
        assert float(a["load_max_over_mean"]) == pytest.approx(1.0)


def test_spans_of_a_batch_come_in_order(spans):
    order = [n for n, *_ in spans if n.startswith("kc.")
             and n not in ("kc.update", "kc.finalize")]
    # batch 0 grows the store between rounds; batch 1 runs once
    assert order[:4] == ["kc.plan", "kc.plan", "kc.run", "kc.sync"]
    assert order[-5:] == ["kc.plan", "kc.plan", "kc.run", "kc.sync",
                          "kc.commit"]
    served = [n for n, *_ in spans if n.split(".")[0] in ("serve", "query")]
    assert served == ["serve.flush", "serve.coalesce", "query.pack",
                      "query.put", "query.run", "query.fetch",
                      "serve.split"]


# --- layer scopes in the executables' HLO metadata --------------------------

def _op_names(hlo_text: str):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _scopes_in(hlo_text: str):
    return {c for n in _op_names(hlo_text) for c in n.split("/")
            if c in scopes.SCOPES}


@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.asarray(jax.devices()[:1]), ("pe",))


@pytest.mark.parametrize("l3_mode", ["dual", "packed"])
def test_update_executable_carries_its_layer_scopes(mesh1, l3_mode):
    cfg = _cfg(l3_mode=l3_mode, store_capacity=1024)
    shape = (64, 60)
    fn = fabsp._update_executable(cfg, mesh1, ("pe",), shape, "uint8",
                                  cfg.slack, 1024)
    reads = jax.ShapeDtypeStruct(shape, jnp.uint8)
    keys = jax.ShapeDtypeStruct((1024,), jnp.uint32)
    counts = jax.ShapeDtypeStruct((1024,), jnp.int32)
    text = fn.lower(reads, keys, counts).compile().as_text()
    assert _scopes_in(text) == {"extract", "l3", "route", "insert"}
    # the scopes do not nest: an op path names at most one layer, so
    # device time splits into disjoint layers
    for name in _op_names(text):
        assert sum(c in scopes.SCOPES for c in name.split("/")) <= 1, name


def test_query_and_finalize_executables_carry_their_layer_scopes(mesh1):
    cfg = _cfg()
    keys = jax.ShapeDtypeStruct((1024,), jnp.uint32)
    counts = jax.ShapeDtypeStruct((1024,), jnp.int32)
    fq = query._query_executable(cfg, mesh1, ("pe",), "uint32", 256, 1024)
    text = fq.lower(jax.ShapeDtypeStruct((256,), jnp.uint32), keys,
                    counts).compile().as_text()
    assert _scopes_in(text) == {"route", "lookup"}
    ff = fabsp._finalize_executable(cfg, mesh1, ("pe",), 1024)
    text = ff.lower(keys, counts).compile().as_text()
    assert _scopes_in(text) == {"finalize"}


# --- the exchange scope on four devices --------------------------------------

def exchange_ops():
    """Run in a child with four CPU devices: prints, for each topology and
    executable, (opcode, op_name) of every op that is an all-to-all or
    whose scope path names `exchange`."""
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices())
    assert devs.size == 4
    keys = jax.ShapeDtypeStruct((4 * 1024,), jnp.uint32)
    counts = jax.ShapeDtypeStruct((4 * 1024,), jnp.int32)
    out = {}
    for topology in ("1d", "2d"):
        axes = ("pe",) if topology == "1d" else ("row", "col")
        mesh = Mesh(devs if topology == "1d" else devs.reshape(2, 2), axes)
        cfg = _cfg(store_capacity=1024, topology=topology)
        shape = (4 * 64, 60)
        upd = fabsp._update_executable(cfg, mesh, axes, shape, "uint8",
                                       cfg.slack, 1024)
        qry = query._query_executable(cfg, mesh, axes, "uint32", 256, 1024)
        texts = {
            "local_update": upd.lower(
                jax.ShapeDtypeStruct(shape, jnp.uint8), keys,
                counts).compile().as_text(),
            "local_query": qry.lower(
                jax.ShapeDtypeStruct((4 * 256,), jnp.uint32), keys,
                counts).compile().as_text()}
        for exe, text in texts.items():
            ops = []
            for line in text.splitlines():
                name = re.search(r'op_name="([^"]*)"', line)
                code = re.match(r"\s*(?:ROOT )?%\S+ = .*? ([a-z][\w-]*)\(",
                                line)
                if name and code and ("all-to-all" == code[1] or "exchange"
                                      in name[1].split("/")):
                    ops.append((code[1], name[1]))
            out[f"{topology}/{exe}"] = ops
    print(json.dumps(out))


@pytest.fixture(scope="module")
def exchange_marks():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT), str(ROOT / "tests")])
    p = subprocess.run(
        [sys.executable, "-c",
         "import test_trace_marks as t; t.exchange_ops()"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("topology", ["1d", "2d"])
@pytest.mark.parametrize("exe", ["local_update", "local_query"])
def test_exchange_scope_holds_the_all_to_alls_alone(exchange_marks,
                                                    topology, exe):
    """On four devices every all-to-all carries the `exchange` scope,
    nested directly in `route`, and the scope holds nothing but the
    lowering of the `all_to_all` primitive (the collective and the layout
    ops made for it): its time reads apart from the partition plan and
    the scatter."""
    ops = exchange_marks[f"{topology}/{exe}"]
    hops = 1 if topology == "1d" else 2
    # a lane list per route: (word) and (word, count) per update chunk,
    # (word, qid) out and (qid, count) back per query
    lanes = 3 if exe == "local_update" else 4
    assert sum(code == "all-to-all" for code, _ in ops) == hops * lanes
    for code, name in ops:
        parts = name.split("/")
        assert "exchange" in parts, (code, name)
        i = parts.index("exchange")
        assert parts[i - 1] == "route" and parts[i + 1:] == ["all_to_all"], \
            name
        assert parts[0] == f"jit({exe})"
