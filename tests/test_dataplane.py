"""Content-addressed data plane: chunked wire format edge cases, digest
dedup at the socket / fabric / MDSS layers, per-direction bandwidth in
placement, cross-run step memoization, budget-aware admission."""
import collections
import contextlib
import socket
import threading
import time

import numpy as np
import pytest

from repro.cloud import Fabric
from repro.cloud.wire import (CHUNK_BYTES, ChannelStore, WireError,
                              content_digest, decode, encode, manifest_of,
                              recv_msg, send_msg)
from repro.core import (AdmissionRefused, CostModel, EmeraldRuntime, MDSS,
                        MigrationManager, Workflow, default_tiers)
from repro.core.scheduler import LocalityPolicy


# ----------------------------------------------------- wire format edges
@pytest.mark.parametrize("value", [{}, [], (), None, {"a": {}, "b": []}])
def test_wire_empty_pytrees(value):
    got = decode(encode(value))
    assert got == value and type(got) is type(value)


def test_wire_zero_length_buffers():
    val = {"z": np.empty((0, 3), np.float32), "w": np.zeros(0),
           "ok": np.arange(2)}
    got = decode(encode(val))
    assert got["z"].shape == (0, 3) and got["z"].dtype == np.float32
    assert got["w"].shape == (0,)
    np.testing.assert_array_equal(got["ok"], np.arange(2))


def test_wire_multi_chunk_frame():
    big = {"x": np.random.rand((3 * CHUNK_BYTES) // 8 + 17)}
    _, chunks = manifest_of(big["x"])
    assert len(chunks) == 4
    got = decode(encode(big))
    np.testing.assert_array_equal(got["x"], big["x"])
    got["x"][0] = -1.0                       # decoded arrays are writable


def test_wire_corrupted_digest_raises_not_hangs():
    data = bytearray(encode({"x": np.random.rand(4096)}, ChannelStore()))
    data[-3] ^= 0xFF                         # flip a payload byte
    with pytest.raises(WireError, match="digest mismatch"):
        decode(bytes(data), ChannelStore())


def test_wire_unknown_reference_raises():
    tx = ChannelStore()
    encode({"x": np.ones(4096)}, tx)         # primes the sender mirror
    ref_frame = encode({"x": np.ones(4096)}, tx)   # all references
    with pytest.raises(WireError, match="unknown chunk digest"):
        decode(ref_frame, ChannelStore())    # receiver never saw them


def test_wire_bad_magic_raises():
    with pytest.raises(WireError, match="magic"):
        decode(b"NOPE" + b"\x00" * 32)


def test_socket_dedup_second_send_is_metadata_only():
    a, b = socket.socketpair()
    sa, sb = ChannelStore(), ChannelStore()
    big = {"x": np.random.rand(1 << 18)}     # 2 MiB
    sizes = []

    def writer():
        sizes.append(send_msg(a, big, sa))
        sizes.append(send_msg(a, big, sa))

    t = threading.Thread(target=writer)
    t.start()
    v1, n1 = recv_msg(b, sb)
    v2, n2 = recv_msg(b, sb)
    t.join()
    a.close(), b.close()
    assert sizes == [n1, n2]
    np.testing.assert_array_equal(v2["x"], big["x"])
    assert n1 > big["x"].nbytes and n2 < 4096
    assert sa.saved_bytes >= big["x"].nbytes


# --------------------------------------------------------- fabric dedup
def test_fabric_warm_reship_and_task_kwargs_dedup():
    val = {"w": np.random.rand(1 << 18)}     # 2 MiB
    with Fabric(workers=1) as f:
        t1 = f.ship(val)
        t2 = f.ship(val)
        np.testing.assert_array_equal(t2.value["w"], val["w"])
        assert t1.bytes_sent > val["w"].nbytes
        assert t2.bytes_sent < 4096          # warm re-ship: metadata only
        # repeated task kwargs dedup the same way
        k1 = f.broker.submit(step="echo", kwargs={"p": val["w"]})
        k1.result(30)
        assert k1.bytes_sent < 4096          # chunks crossed in the ships


def test_fabric_dedup_off_ships_everything():
    val = {"w": np.random.rand(1 << 16)}     # 512 KiB
    with Fabric(workers=1, dedup=False) as f:
        f.ship(val)
        t2 = f.ship(val)
        assert t2.bytes_sent > val["w"].nbytes
        assert t2.bytes_received > val["w"].nbytes


# ----------------------------------------------------------- MDSS dedup
def make_mgr():
    tiers = default_tiers()
    cm = CostModel(tiers)
    return MigrationManager(tiers, MDSS(tiers, cost_model=cm), cm)


def test_mdss_cross_namespace_content_dedup():
    mgr = make_mgr()
    mdss = mgr.mdss
    big = np.random.rand(1 << 17)            # 1 MiB
    mdss.put("a/params", big, tier="local")
    moved = mdss.ensure(["a/params"], "cloud")
    assert moved == big.nbytes               # cold: full freight
    # same content under another namespace: the cloud tier already holds
    # every chunk, so the transfer obligation is zero
    mdss.put("b/params", big.copy(), tier="local")
    assert mdss.stale_bytes(["b/params"], "cloud") == 0
    assert mdss.ensure(["b/params"], "cloud") == 0
    assert mdss.has_latest("b/params", "cloud")
    # and dropping ONE namespace keeps the other's chunks resident
    mdss.drop_namespace("a")
    assert mdss.tier_chunk_stats("cloud")[0] > 0
    mdss.drop_namespace("b")
    assert mdss.tier_chunk_stats("cloud") == (0, 0)


def test_mdss_distinct_content_still_charged():
    mgr = make_mgr()
    mdss = mgr.mdss
    mdss.put("a/x", np.zeros(1 << 14), tier="local")
    mdss.ensure(["a/x"], "cloud")
    mdss.put("b/x", np.ones(1 << 14), tier="local")
    assert mdss.stale_bytes(["b/x"], "cloud") == (1 << 14) * 8


def test_placement_cost_charges_only_nonduplicate_bytes():
    mgr = make_mgr()
    cm, mdss = mgr.cost_model, mgr.mdss
    pol = LocalityPolicy(cm, mdss, "cloud")
    wf = Workflow("dp")
    wf.var("a")
    s = wf.step("s", lambda **kw: {"y": np.float64(0)}, inputs=("a",),
                outputs=("y",), remotable=True, jax_step=False)
    big = np.random.rand(1 << 17)
    mdss.put("other/warm", big, tier="cloud")    # same content, other URI
    mdss.put("a", big.copy(), tier="local")
    cm.stats_for("s").measured_s.update(local=0.001, cloud=0.001)
    d = pol.place(s)
    # the cloud tier holds a's content (under another entry): no staging
    # charge, so equal exec estimates make cloud win on the tie-break
    assert d.stale_bytes["cloud"] == 0 and d.offload


def test_namespace_reuse_does_not_resurrect_stale_digests():
    """drop_namespace resets versions to 1 on reuse: the manifest cache
    must not hand the OLD content's digest to the new data (a stale hit
    would collide memo keys across unrelated submissions)."""
    mgr = make_mgr()
    mdss = mgr.mdss
    mdss.put("exp/P", np.zeros(256), tier="local")
    d1 = mdss.content_digest("exp/P")
    mdss.drop_namespace("exp")
    mdss.put("exp/P", np.ones(256), tier="local")    # version 1 again
    assert mdss.content_digest("exp/P") != d1


def test_content_digest_tracks_value_not_uri():
    mgr = make_mgr()
    mdss = mgr.mdss
    v = np.random.rand(256)
    mdss.put("p/x", v, tier="local")
    mdss.put("q/y", v.copy(), tier="cloud")
    assert mdss.content_digest("p/x") == mdss.content_digest("q/y")
    mdss.put("p/x", v + 1, tier="local")
    assert mdss.content_digest("p/x") != mdss.content_digest("q/y")
    assert content_digest({"a": v}) != content_digest({"b": v})


def test_host_copy_before_hashing_keeps_every_digest():
    """The store copies device arrays to the host as a step of its own
    (``to_host``) before hashing. Manifests are what hashing the device
    value gave: the digests below were computed on the same value before
    that split existed."""
    import jax.numpy as jnp

    from repro.cloud.wire import to_host
    value = {"b": jnp.arange(300_000, dtype=jnp.float32).reshape(300, 1000),
             "a": (np.arange(5, dtype=np.int32), [jnp.float32(2.5)]),
             "c": (jnp.ones(3, jnp.bfloat16), 7), "d": np.float64(1.5),
             "e": None}
    want = manifest_of(value)
    assert want[0].hex() == "6e62018a64f7fe24f713540ea938ed0a"
    assert [d.hex() for d, _ in want[1]] == [
        "a9179a1d3a7953e8b9ebe28512a060b5",
        "56fab19886f026c3b1dee10afc472648",
        "e528f4309e1413e6bc35aea5d8db8519",
        "072e3304b03423a4767d28c5fed09f81",
        "dc6a48767bd84de83df12675d0f9e490"]
    host = to_host(value)
    assert isinstance(host["b"], np.ndarray) and host["e"] is None \
        and isinstance(host["a"][1][0], np.ndarray) and host["c"][1] == 7
    assert manifest_of(host) == want
    mdss = make_mgr().mdss
    assert mdss._manifest(value) == want
    assert mdss.bytes_hashed == 300_000 * 4 + 5 * 4 + 4 + 3 * 2 + 8 + 8
    # a namedtuple keeps its type through the host copy
    pair = HostPair(x=jnp.ones(4), y="tag")
    assert type(to_host(pair)) is HostPair
    assert manifest_of(to_host(pair)) == manifest_of(pair)


HostPair = collections.namedtuple("HostPair", "x y")


# ------------------------------------ deferred manifests of device values
class PhaseLog:
    """Annotation factory that records the names a tracer opens."""

    def __init__(self):
        self.names = []

    def __call__(self, name, **attrs):
        self.names.append(name)
        return contextlib.nullcontext()


def traced_mdss():
    from repro.obs.tracing import Tracer
    mdss = make_mgr().mdss
    mdss.tracer = Tracer()
    mdss.tracer.annotation = log = PhaseLog()
    return mdss, log


def test_device_value_install_is_not_hashed():
    import jax.numpy as jnp
    mdss, log = traced_mdss()
    mdss.put("a/v", jnp.arange(4096, dtype=jnp.float32), tier="local")
    mdss.put_many({"a/w": jnp.ones(8), "a/h": np.ones(8)}, tier="cloud")
    assert mdss.manifests_deferred == 2 and mdss.manifests_on_demand == 0
    assert mdss.bytes_hashed == np.ones(8).nbytes      # the host value only
    assert log.names.count("emerald:hash") == 1
    assert log.names.count("emerald:d2h") == 1
    assert mdss._manifest_cache.keys() == {("a/h", 1)}


def test_content_digest_of_a_device_value_is_computed_once():
    import jax.numpy as jnp

    from repro.cloud.wire import to_host
    mdss, log = traced_mdss()
    v = {"m": jnp.arange(4096, dtype=jnp.float32), "s": jnp.float32(2)}
    mdss.put("a/v", v, tier="local")
    assert mdss.bytes_hashed == 0 and log.names == []
    assert mdss.content_digest("a/v") == manifest_of(to_host(v))[0]
    assert mdss.manifests_on_demand == 1
    hashed = mdss.bytes_hashed
    assert hashed == 4096 * 4 + 4
    assert mdss.content_digest("a/v") == manifest_of(to_host(v))[0]
    assert mdss.bytes_hashed == hashed and mdss.manifests_on_demand == 1
    assert log.names == ["emerald:d2h", "emerald:hash"]


def test_device_value_staged_elsewhere_owes_its_full_bytes():
    import jax.numpy as jnp
    mdss, log = traced_mdss()
    v = jnp.arange(4096, dtype=jnp.float32)
    mdss.put("a/v", v, tier="local")
    mdss.put("b/v", v, tier="cloud")       # same content, already on cloud
    assert mdss.stale_bytes(["a/v"], "cloud") == v.nbytes
    assert mdss.stale_bytes(["a/v"], "local") == 0
    assert mdss.ensure(["a/v"], "cloud") == v.nbytes
    assert mdss.stale_bytes(["a/v"], "cloud") == 0
    assert mdss.bytes_hashed == 0 and log.names == []
    assert mdss._manifest_cache == {}
    assert mdss.tier_chunk_stats("cloud") == (0, 0)


class ChunkAwareTransport:
    """Stands in for a transport that ships values by their manifests."""

    def __init__(self):
        self.chunks = []

    def transfer_ex(self, value, src, dst, chunks=None, missing_bytes=None):
        self.chunks.append(chunks)
        return value, missing_bytes


def test_chunk_aware_ship_computes_a_device_value_manifest():
    import jax.numpy as jnp
    mdss = make_mgr().mdss
    mdss.transport = ChunkAwareTransport()
    v = jnp.arange(4096, dtype=jnp.float32)
    mdss.put("a/v", v, tier="local")
    assert mdss.bytes_hashed == 0
    assert mdss.stale_bytes(["a/v"], "cloud") == v.nbytes
    assert mdss.ensure(["a/v"], "cloud") == v.nbytes
    assert mdss.transport.chunks == [manifest_of(np.asarray(v))[1]]
    assert mdss.manifests_on_demand == 1 and mdss.bytes_hashed == v.nbytes
    # the shipped copy was installed with its manifest known: indexed
    assert mdss.tier_chunk_stats("cloud") == (1, v.nbytes)
    assert mdss.tier_chunk_stats("local") == (0, 0)


def chunk_index_from_copies(mdss, tier):
    """The tier's chunk index rebuilt from the copies recorded as indexed."""
    idx = collections.Counter()
    lengths = {}
    for (uri, t), chunks in mdss._copy_chunks.items():
        assert tier != t or tier in mdss._entries[uri].copies
        if t == tier:
            for d, ln in chunks:
                idx[d] += 1
                lengths[d] = ln
    return idx, lengths


def assert_index_in_lockstep(mdss):
    for (uri, t) in mdss._copy_chunks:
        assert t in mdss._entries[uri].copies
    for tier in mdss.tiers:
        idx, lengths = chunk_index_from_copies(mdss, tier)
        live = mdss._tier_chunks.get(tier, {})
        assert all(ref > 0 for ref, _ in live.values())
        assert {d: ref for d, (ref, _) in live.items()} == dict(idx)
        assert mdss.tier_chunk_stats(tier) == (len(idx),
                                               sum(lengths.values()))


@pytest.mark.parametrize("seed", range(6))
def test_chunk_index_stays_in_lockstep_with_mixed_values(seed):
    """Host and device puts, stagings across tiers, digests demanded of
    deferred values and namespace drops, in a seeded order: the chunk
    index always equals the one rebuilt from the indexed copies, and is
    empty once every namespace is gone."""
    import random

    import jax.numpy as jnp
    rng = random.Random(seed)
    mdss = make_mgr().mdss
    host = [np.full(256, k, np.float32) for k in range(3)]
    device = [jnp.asarray(h) for h in host]
    tiers = list(mdss.tiers)
    uris = [f"{ns}/{leaf}" for ns in ("a", "b") for leaf in ("x", "y")]
    for _ in range(60):
        op = rng.choice(("put", "put", "ensure", "digest", "drop"))
        uri = rng.choice(uris)
        if op == "put":
            pool = rng.choice((host, device))
            mdss.put(uri, pool[rng.randrange(3)], tier=rng.choice(tiers))
        elif op == "ensure" and uri in mdss._entries:
            mdss.ensure([uri], rng.choice(tiers))
        elif op == "digest" and uri in mdss._entries:
            mdss.content_digest(uri)
        elif op == "drop":
            mdss.drop_namespace(uri.split("/")[0])
        assert_index_in_lockstep(mdss)
    for ns in ("a", "b"):
        mdss.drop_namespace(ns)
    assert_index_in_lockstep(mdss)
    assert mdss._copy_chunks == {}
    assert all(mdss.tier_chunk_stats(t) == (0, 0) for t in tiers)


# ------------------------------------------------- asymmetric placement
def test_placement_tracks_asymmetric_link():
    """Force an asymmetric link: a fast up (local->cloud), slow down
    (cloud->local). The locality scorer must charge each direction at
    its own observed bandwidth — staging TO cloud is cheap, staging the
    same bytes home is not."""
    mgr = make_mgr()
    cm, mdss = mgr.cost_model, mgr.mdss
    cm.observe_bandwidth("local", "cloud", 1e9, 1.0)    # 1 GB/s up
    cm.observe_bandwidth("cloud", "local", 1e9, 100.0)  # 10 MB/s down
    wf = Workflow("asym")
    wf.var("a")
    s = wf.step("s", lambda **kw: {"y": np.float64(0)}, inputs=("a",),
                outputs=("y",), remotable=True, jax_step=False)
    mdss.put("a", np.random.rand(1 << 20), tier="local")   # 8 MiB, local
    cm.stats_for("s").measured_s.update(local=0.01, cloud=0.01)
    pol = LocalityPolicy(cm, mdss, "cloud")
    d = pol.place(s)
    # staging UP rides the fast leg: the cloud score carries only ~8 ms
    # of transfer on top of equal exec
    assert d.stale_bytes["cloud"] == 8 << 20
    assert d.scores["cloud"] < 0.05
    # new content on cloud: bringing it home pays the slow DOWN leg —
    # two orders of magnitude worse for the same bytes
    mdss.put("a", np.random.rand(1 << 20), tier="cloud")
    d2 = pol.place(s)
    assert d2.offload and d2.scores["local"] > 0.5
    # the directional estimates really differ
    assert cm.transfer_time(8 << 20, "cloud", "local") > \
        10 * cm.transfer_time(8 << 20, "local", "cloud")


@pytest.mark.slow
def test_fabric_feeds_per_direction_bandwidth():
    from repro.cloud import attach
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    with Fabric(workers=1, dedup=False) as fabric:
        attach(tiers, fabric, mdss=mdss, cost_model=cm)
        mdss.put("big", np.random.rand(1 << 20), tier="local")   # 8 MiB
        mdss.ensure(["big"], "cloud")
    assert cm.measured_bw.get(("local", "cloud"), 0) > 0
    assert cm.measured_bw.get(("cloud", "local"), 0) > 0


# -------------------------------------------------- cross-run memoization
HEAVY_CALLS = []
_heavy_lock = threading.Lock()


def heavy_step(P):
    with _heavy_lock:
        HEAVY_CALLS.append(threading.get_ident())
    time.sleep(0.15)
    return {"out": np.asarray(P).sum() * np.ones(16)}


def make_tenant(name):
    wf = Workflow(name)
    wf.var("P")
    wf.step("heavy", heavy_step, inputs=("P",), outputs=("out",),
            remotable=True, jax_step=False)
    return wf


@pytest.mark.parametrize("device", [False, True])
def test_memoized_duplicate_submission_executes_once(device):
    """Device inputs carry no manifest from their install: the memo key
    computes their digests on demand and still shares one execution."""
    import jax.numpy as jnp
    HEAVY_CALLS.clear()
    P = np.random.rand(1 << 14)
    if device:
        P = jnp.asarray(P)
    with EmeraldRuntime(memoize=True) as rt:
        h1 = rt.submit(make_tenant("t1"), {"P": P}, fetch=["out"])
        h2 = rt.submit(make_tenant("t2"), {"P": P}, fetch=["out"])
        r1, r2 = h1.result(60), h2.result(60)
    np.testing.assert_array_equal(r1["out"], r2["out"])
    assert len(HEAVY_CALLS) == 1
    execs = [e for h in (h1, h2) for e in h.events
             if e.kind in ("local", "offload") and e.step == "heavy"]
    assert sorted(e.info["memo_hit"] for e in execs) == [False, True]
    assert rt.manager.memo_hits == 1
    # each tenant's P: its digest computed for the memo key, not at install
    assert rt.mdss.manifests_on_demand == (2 if device else 0)


def test_memoization_respects_input_content():
    HEAVY_CALLS.clear()
    with EmeraldRuntime(memoize=True) as rt:
        h1 = rt.submit(make_tenant("t1"), {"P": np.zeros(64)})
        h2 = rt.submit(make_tenant("t2"), {"P": np.ones(64)})
        h1.result(60), h2.result(60)
    assert len(HEAVY_CALLS) == 2             # different inputs: no sharing


def test_memoization_default_off_and_per_step_override():
    HEAVY_CALLS.clear()
    P = np.random.rand(64)
    with EmeraldRuntime() as rt:             # memoize unset: off
        rt.submit(make_tenant("t1"), {"P": P}).result(60)
        rt.submit(make_tenant("t2"), {"P": P}).result(60)
    assert len(HEAVY_CALLS) == 2
    HEAVY_CALLS.clear()
    with EmeraldRuntime(memoize=True) as rt:
        wf1, wf2 = make_tenant("t1"), make_tenant("t2")
        wf2.steps["heavy"].memoizable = False    # step-level veto
        rt.submit(wf1, {"P": P}).result(60)
        rt.submit(wf2, {"P": P}).result(60)
    assert len(HEAVY_CALLS) == 2


def test_memoized_results_are_not_aliased_between_tenants():
    P = np.random.rand(64)
    with EmeraldRuntime(memoize=True) as rt:
        h1 = rt.submit(make_tenant("t1"), {"P": P}, fetch=["out"])
        h2 = rt.submit(make_tenant("t2"), {"P": P}, fetch=["out"])
        r1, r2 = h1.result(60), h2.result(60)
        r1["out"][0] = -999.0                # tenant 1 scribbles on its copy
        r2["out"][1] = -888.0
        h3 = rt.submit(make_tenant("t3"), {"P": P}, fetch=["out"])
        r3 = h3.result(60)                   # memo hit off the cached entry
    assert r2["out"][0] != -999.0
    assert r3["out"][0] != -999.0 and r3["out"][1] != -888.0


def test_memoized_failure_does_not_poison_the_key():
    from repro.core import StepFailure
    calls = []

    def flaky(P):
        calls.append(1)
        if len(calls) == 1:
            raise StepFailure("first attempt dies")   # retryable failure
        return {"out": np.float64(1.0)}

    wf = Workflow("flaky")
    wf.var("P")
    # retries=0: one cloud attempt then the local fallback lane
    wf.step("heavy", flaky, inputs=("P",), outputs=("out",),
            remotable=True, jax_step=False, retries=0)
    with EmeraldRuntime(memoize=True) as rt:
        out = rt.submit(wf, {"P": np.zeros(4)}, fetch=["out"]).result(60)
    assert float(out["out"]) == 1.0 and len(calls) == 2


# ---------------------------------------------- budget-aware admission
def tiny_wf(name="t"):
    wf = Workflow(name)
    wf.var("x")
    wf.step("s", lambda x: {"y": np.float64(float(x) + 1)}, inputs=("x",),
            outputs=("y",), remotable=False, jax_step=False)
    return wf


def test_admission_refuses_budget_over_remaining_capacity():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm, capacity_bytes=100 << 20)
    mgr = MigrationManager(tiers, mdss, cm)
    with EmeraldRuntime(mgr, admission_headroom=1.0) as rt:
        gate = threading.Event()
        wf = Workflow("hold")
        wf.var("x")
        wf.step("s", lambda x: (gate.wait(30), {"y": np.float64(0)})[1],
                inputs=("x",), outputs=("y",), remotable=False,
                jax_step=False)
        h1 = rt.submit(wf, {"x": np.float64(0)},
                       residency_budget={"cloud": 60 << 20})
        # occupancy is ~zero, but 60 MiB is already spoken for: a second
        # 60 MiB declaration exceeds REMAINING capacity and is refused
        with pytest.raises(AdmissionRefused, match="remaining capacity"):
            rt.submit(tiny_wf(), {"x": np.float64(0)},
                      residency_budget={"cloud": 60 << 20})
        # an undeclared (occupancy-only) submission still admits
        h3 = rt.submit(tiny_wf("free"), {"x": np.float64(0)})
        gate.set()
        h1.result(60), h3.result(60)
        # h1 finished: its reservation is released, the budget now fits
        h4 = rt.submit(tiny_wf("later"), {"x": np.float64(0)},
                       residency_budget={"cloud": 60 << 20})
        h4.result(60)


def test_failed_submit_releases_its_reservation():
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm, capacity_bytes=100 << 20)
    mgr = MigrationManager(tiers, mdss, cm)
    with EmeraldRuntime(mgr, admission_headroom=1.0) as rt:
        # a submission that reserves its budget but fails before the
        # driver takes ownership must not leak the reservation
        with pytest.raises(ValueError):
            rt.submit(tiny_wf(), {"x": np.float64(0)}, policy="no-such",
                      residency_budget={"cloud": 60 << 20})
        h = rt.submit(tiny_wf("ok"), {"x": np.float64(0)},
                      residency_budget={"cloud": 60 << 20})
        h.result(60)
