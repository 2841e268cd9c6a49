"""Distributed tier placement + transport: the defer path never gathers on
host, only deferred examples' bytes cross a placement boundary, and pod
placement puts tiers on disjoint device sets."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import cascade, ensemble as ens
from repro.core.cascade import TierSpec, bucket_chunks
from repro.models.params import unbox
from repro.serve import (
    CascadeServer,
    CascadeTier,
    Request,
    SimulatedLinkTransport,
    edge_cloud,
    single_host,
)

REPO = os.path.join(os.path.dirname(__file__), "..")

SMALL = ModelConfig(
    name="tiny-s", family="dense", n_layers=2, d_model=64, d_ff=128,
    vocab_size=64, n_heads=4, n_kv_heads=2, remat=False,
)
BIG = ModelConfig(
    name="tiny-b", family="dense", n_layers=3, d_model=96, d_ff=192,
    vocab_size=64, n_heads=4, n_kv_heads=4, remat=False,
)


@pytest.fixture(scope="module")
def stacks():
    v1, _ = unbox(ens.init_ensemble(SMALL, 3, jax.random.PRNGKey(0)))
    v2, _ = unbox(ens.init_ensemble(BIG, 1, jax.random.PRNGKey(1)))
    return v1, v2


def _two_tier(stacks, placement=None):
    v1, v2 = stacks
    return CascadeServer(
        [
            CascadeTier(SMALL, v1, TierSpec("t1", "vote", 0.67, k=3, cost=1.0)),
            CascadeTier(BIG, v2, TierSpec("t2", "confidence", -1.0, k=1, cost=50.0)),
        ],
        placement=placement,
    )


# ---------------------------------------------------------------------------
# no host gathers on the defer path
# ---------------------------------------------------------------------------


def test_routed_defer_path_no_host_gather(stacks):
    """The routed cascade under a device->host transfer guard: any IMPLICIT
    device->host transfer (a host gather/re-pad of the payload) raises.
    Intentional reads all go through cascade._fetch, whose byte meter must
    see only per-tier count scalars plus the final (B,) results."""
    server = _two_tier(stacks, single_host(2))
    B, S = 16, 12
    toks = np.random.default_rng(2).integers(0, 64, (B, S)).astype(np.int32)
    cascade.reset_host_fetch_stats()
    with jax.transfer_guard_device_to_host("disallow"):
        res = server.classify(toks)
    assert res.tier_counts.sum() == B
    stats = cascade.host_fetch_stats()
    # final results: pred+tier_of (i32) + scores (f32) + 2 tier counts;
    # per-transition: one count scalar.  Everything else stayed on device.
    result_bytes = B * 4 * 3 + 2 * 4
    scalar_bytes = 4
    assert stats["bytes"] <= result_bytes + scalar_bytes, stats
    # the payload (B x S tokens) dwarfs that bound — none of it was fetched
    assert stats["bytes"] < B * S * 4


def test_routed_matches_legacy_host_semantics(stacks):
    """Device routing is a pure implementation change: results equal the
    dense reference executor's on the shared semantics."""
    from repro.core.cascade import cascade_apply_dense

    v1, v2 = stacks
    server = _two_tier(stacks)
    toks = np.random.default_rng(3).integers(0, 64, (16, 12)).astype(np.int32)
    res = server.classify(toks)

    fns = [
        lambda b, t=server.tiers[0]: t._last_logits(t.values, {"tokens": b["tokens"]}),
        lambda b, t=server.tiers[1]: t._last_logits(t.values, {"tokens": b["tokens"]}),
    ]
    pred, tier_of, _ = cascade_apply_dense(
        fns, [t.spec for t in server.tiers], {"tokens": jnp.asarray(toks)}
    )
    np.testing.assert_array_equal(res.pred, np.asarray(pred))
    np.testing.assert_array_equal(res.tier_of, np.asarray(tier_of))


# ---------------------------------------------------------------------------
# transport: only deferred examples' bytes cross
# ---------------------------------------------------------------------------


def test_edge_cloud_transport_meters_only_deferrals(stacks):
    from repro.core import deferral

    v1, v2 = stacks
    B, S = 16, 12
    toks = np.random.default_rng(4).integers(0, 64, (B, S)).astype(np.int32)
    # median-confidence threshold -> about half the batch defers, so the
    # metered traffic must be strictly the deferred slice, not the batch
    t1_probe = CascadeTier(SMALL, v1, TierSpec("t1", "confidence", 0.0, k=3, cost=1.0))
    logits = t1_probe._last_logits(t1_probe.values, {"tokens": jnp.asarray(toks)})
    theta = float(np.median(np.asarray(deferral.confidence_rule(logits, 0.0).score)))

    placement = edge_cloud(delay="medium")
    server = CascadeServer(
        [
            CascadeTier(SMALL, v1, TierSpec("t1", "confidence", theta, k=3, cost=1.0)),
            CascadeTier(BIG, v2, TierSpec("t2", "confidence", -1.0, k=1, cost=50.0)),
        ],
        placement=placement,
    )
    res = server.classify(toks)
    link = placement.link(0)
    n_def = int(res.tier_counts[1])
    assert 0 < n_def < B
    assert link.total_examples == n_def
    # payload = deferred tokens rows + the i32 routing index, padded to the
    # pow2 bucket cover — never the full batch
    n_pad = min(sum(bucket_chunks(n_def, server.pad_to)), B)
    assert link.total_bytes == n_pad * (S * 4 + 4)
    assert link.total_bytes < B * S * 4
    assert link.total_latency == pytest.approx(0.1)  # one metered hop


def test_no_deferrals_no_traffic(stacks):
    """Unanimous tier 1 -> the link carries zero bytes (the 14x claim's
    limiting case)."""
    v1, v2 = stacks
    one = ens.take_member(v1, 0)
    same = jax.tree.map(lambda x: jnp.stack([x, x, x]), one)
    placement = edge_cloud(delay="large")
    server = CascadeServer(
        [
            CascadeTier(SMALL, same, TierSpec("t1", "vote", 0.99, k=3, cost=1.0)),
            CascadeTier(BIG, v2, TierSpec("t2", "confidence", -1.0, k=1, cost=50.0)),
        ],
        placement=placement,
    )
    toks = np.random.default_rng(5).integers(0, 64, (16, 12)).astype(np.int32)
    res = server.classify(toks)
    assert res.tier_counts[0] == 16
    assert placement.link(0).total_bytes == 0
    assert placement.link(0).total_latency == 0.0


def test_simulated_link_latency_and_bandwidth():
    tr = SimulatedLinkTransport(delay=0.01, bandwidth=1e6)
    payload = {"x": jnp.ones((4, 250), jnp.float32)}  # 4000 B
    out = tr.send("edge0", "cloud0", payload, n_examples=4)
    np.testing.assert_array_equal(np.asarray(out["x"]), np.asarray(payload["x"]))
    assert tr.total_bytes == 4000
    assert tr.total_latency == pytest.approx(0.01 + 4000 / 1e6)
    assert tr.hops[0].src == "edge0" and tr.hops[0].dst == "cloud0"


def test_serve_continuous_requeue_crosses_link(stacks):
    """Continuous-batching deferral re-queue is a metered transport hop:
    exactly the deferred requests' prompts cross edge->cloud."""
    placement = edge_cloud(delay="small")
    server = _two_tier(stacks, placement)
    rng = np.random.default_rng(6)
    reqs = [
        Request(tokens=rng.integers(0, 64, 8).astype(np.int32), max_new_tokens=3)
        for _ in range(5)
    ]
    done = server.serve_continuous(reqs, n_slots=2, max_seq=32)
    assert len(done) == 5
    n_def = sum(1 for r in done if r.tier == 1)
    link = placement.link(0)
    assert link.total_examples == n_def
    assert link.total_bytes == n_def * 8 * 4  # each deferred prompt, once


# ---------------------------------------------------------------------------
# pod placement: tiers on disjoint device sets (subprocess forces 8 devices)
# ---------------------------------------------------------------------------

_POD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
import jax.numpy as jnp
from repro.configs.base import ModelConfig
from repro.core import ensemble as ens
from repro.core.cascade import TierSpec
from repro.models.params import unbox
from repro.serve import CascadeServer, CascadeTier
from repro.serve.placement import hosts_disjoint, pod_placement

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
pl = pod_placement(mesh, 2)
assert [h.name for h in pl.hosts] == ["pod0", "pod1"]
assert hosts_disjoint(pl), "pod slices must own disjoint devices"
assert len(pl.hosts[0].devices() & pl.hosts[1].devices()) == 0
from repro.serve.transport import ShardedDevicePutTransport
assert isinstance(pl.link(0), ShardedDevicePutTransport)  # sharded default

SMALL = ModelConfig(name="tiny-s", family="dense", n_layers=2, d_model=64,
    d_ff=128, vocab_size=64, n_heads=4, n_kv_heads=2, remat=False)
BIG = ModelConfig(name="tiny-b", family="dense", n_layers=2, d_model=64,
    d_ff=128, vocab_size=64, n_heads=4, n_kv_heads=4, remat=False)
v1, _ = unbox(ens.init_ensemble(SMALL, 2, jax.random.PRNGKey(0)))
v2, _ = unbox(ens.init_ensemble(BIG, 1, jax.random.PRNGKey(1)))

toks = np.random.default_rng(2).integers(0, 64, (16, 8)).astype(np.int32)
# median-confidence threshold -> partial deferral, so 'only the deferred
# slice crossed' is a strict statement
from repro.core import deferral
probe = CascadeTier(SMALL, v1, TierSpec("t1", "confidence", 0.0, k=2, cost=1.0))
logits = probe._last_logits(probe.values, {"tokens": jnp.asarray(toks)})
theta = float(np.median(np.asarray(deferral.confidence_rule(logits, 0.0).score)))

def serve(placement):
    server = CascadeServer([
        CascadeTier(SMALL, v1, TierSpec("t1", "confidence", theta, k=2, cost=1.0)),
        CascadeTier(BIG, v2, TierSpec("t2", "confidence", -1.0, k=1, cost=50.0)),
    ], placement=placement)
    return server, server.classify(toks)

server, res = serve(pl)

# tier weights actually live on their pod slice
d0 = {d for l in jax.tree.leaves(server.tiers[0].values) for d in l.devices()}
d1 = {d for l in jax.tree.leaves(server.tiers[1].values) for d in l.devices()}
assert d0 <= pl.hosts[0].devices(), (d0, pl.hosts[0].devices())
assert d1 <= pl.hosts[1].devices(), (d1, pl.hosts[1].devices())

res_counts = res.tier_counts
assert res_counts.sum() == 16
link = pl.link(0)
n_def = int(res_counts[1])
assert 0 < n_def < 16, n_def
assert link.total_examples == n_def, (link.total_examples, n_def)
assert 0 < link.total_bytes < 16 * (8 * 4 + 4)  # only the deferred slice

# -- sharded hand-off parity vs the replicated baseline --------------------
# the delivered payload's example axis must really shard over the dst
# slice ('pod' x 'data' = 2 shards here), and results/metered traffic must
# be identical to pod-wide replication
h = link.send_async("pod0", "pod1",
                    {"x": jnp.ones((8, 4), jnp.float32)}, n_examples=8)
delivered = h.result()["x"]
shards = {s.data.shape for s in delivered.addressable_shards}
assert shards == {(4, 4)}, shards  # 8 rows -> 2 shards of 4, not replicas
assert link.shard_counts({"x": jnp.ones((8, 4), jnp.float32)}) == [2]
link.hops.pop()  # probe hop: keep the serving meters comparable below

pl_rep = pod_placement(mesh, 2, shard_examples=False)
_, res_rep = serve(pl_rep)
np.testing.assert_array_equal(res.pred, res_rep.pred)
np.testing.assert_array_equal(res.tier_of, res_rep.tier_of)
link_rep = pl_rep.link(0)
assert link_rep.total_bytes == link.total_bytes, (
    link_rep.total_bytes, link.total_bytes)
assert link_rep.total_examples == link.total_examples

# -- chunked re-feeds keep the landed sharding (no replicated intermediate) -
# a 0.75-quantile threshold defers ~12 of 16 rows -> the tier-2 cover needs
# TWO pow2 chunks (8 + 4), so every chunk goes through the slice/pad path
# that cascade_apply_routed must re-place onto the transport's example
# sharding; each fed chunk must arrive 2-way example-sharded, never as
# pod-wide replicas
score = np.asarray(deferral.confidence_rule(logits, 0.0).score)
theta_hi = float(np.quantile(score, 0.75))
server3 = CascadeServer([
    CascadeTier(SMALL, v1, TierSpec("t1", "confidence", theta_hi, k=2, cost=1.0)),
    CascadeTier(BIG, v2, TierSpec("t2", "confidence", -1.0, k=1, cost=50.0)),
], placement=pod_placement(mesh, 2))
shard_log = []
t2 = server3.tiers[1]
orig_logits_fn = t2._last_logits
def spy(values, batch):
    fed = batch["tokens"]
    shard_log.append((int(fed.shape[0]),
                      {s.data.shape for s in fed.addressable_shards}))
    return orig_logits_fn(values, batch)
t2._last_logits = spy
res3 = server3.classify(toks)
n_def3 = int(res3.tier_counts[1])
assert n_def3 > 8, n_def3  # must need a multi-bucket (8 + 4) cover
assert len(shard_log) >= 2, shard_log
for rows, shapes in shard_log:
    assert len(shapes) == 1, (rows, shapes)
    (shape,) = shapes
    assert shape[0] * 2 == rows, (
        "tier-2 chunk fed replicated (or mis-sharded): rows=%d shards=%r"
        % (rows, shapes))
# every program of a slice of several devices runs per device (shard_map),
# whatever the kernel impl, and the slice's chips share the examples
t1 = server.tiers[0]
text = t1._last_logits.lower(t1.values, {"tokens": jnp.asarray(toks)}).as_text()
assert "manual" in text, "tier program not run per device"
out = t1._last_logits(t1.values, {"tokens": jnp.asarray(toks)})
assert {s.data.shape[1] for s in out.addressable_shards} == {8}, (
    "16 examples over a 2-way 'data' axis: 8 per chip")
print("POD_PLACEMENT_OK", n_def, link.total_bytes)
"""


def test_pod_placement_disjoint_hosts_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", _POD_SCRIPT],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True, text=True, timeout=560,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "POD_PLACEMENT_OK" in r.stdout


# ---------------------------------------------------------------------------
# pod placement with the kernels on: each slice runs its programs per device
# ---------------------------------------------------------------------------

_POD_KERNEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from jax.sharding import Mesh
from repro.configs.base import ModelConfig
from repro.core import ensemble as ens
from repro.core.cascade import TierSpec
from repro.kernels import config as kcfg
from repro.models.params import unbox
from repro.serve import CascadeServer, CascadeTier, Request, ServeConfig
from repro.serve.placement import pod_placement, single_host

SMALL = ModelConfig(name="tiny-ks", family="dense", n_layers=2, d_model=64,
    d_ff=128, vocab_size=64, n_heads=4, n_kv_heads=2, remat=False)
BIG = ModelConfig(name="tiny-kb", family="dense", n_layers=2, d_model=64,
    d_ff=128, vocab_size=64, n_heads=4, n_kv_heads=4, remat=False)
v1, _ = unbox(ens.init_ensemble(SMALL, 2, jax.random.PRNGKey(0)))
v2, _ = unbox(ens.init_ensemble(BIG, 1, jax.random.PRNGKey(1)))
# the chip's (2, 2, 1) layout: tier 0 on devices {0, 1}, tier 1 on {2, 3}
mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 1), ("pod", "data", "model"))
rng = np.random.default_rng(3)
toks = rng.integers(0, 64, (8, 16)).astype(np.int32)
prompts = [rng.integers(0, 64, (n,)).astype(np.int32) for n in (9, 17, 5)]

def run(placement):
    server = CascadeServer([
        CascadeTier(SMALL, v1, TierSpec("t0", "vote", 0.67, k=2, cost=1.0)),
        CascadeTier(BIG, v2, TierSpec("t1", "confidence", -1.0, k=1, cost=5.0)),
    ], placement=placement)
    res = server.classify(toks)
    done = server.serve_continuous(
        [Request(tokens=p, max_new_tokens=4) for p in prompts],
        ServeConfig(n_slots=2, max_seq=32, paged=True))
    out = sorted((len(r.tokens), r.tier, r.output.tolist()) for r in done)
    return server, res, out

with kcfg.use_impl("pallas_interpret"):
    pod, res_pod, out_pod = run(pod_placement(mesh, 2))
    # a jit over a 2-device slice cannot partition a kernel: the slice's
    # programs must lower through shard_map (manual sharding)
    t0 = pod.tiers[0]
    text = t0._last_logits.lower(t0.values, {"tokens": toks}).as_text()
    assert "manual" in text, "tier program not run per device"
    # the slice's two chips share the examples: 8 rows -> 4 per chip
    out = t0._last_logits(t0.values, {"tokens": toks})
    assert {s.data.shape[1] for s in out.addressable_shards} == {4}
    # a copy-on-write page copy on a slice's stacked pools (no weights)
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import api
    from repro.serve.cascade_server import tier_paged_programs
    t1 = pod.tiers[1]
    pool = unbox(api.init_paged_pool(BIG, 5, 8))[0]
    pools = jax.device_put(
        jax.tree.map(lambda a: jnp.arange(a.size).reshape(a.shape)
                     .astype(a.dtype)[None], pool),
        NamedSharding(t1.mesh, P()))
    got = tier_paged_programs(BIG, 0.0, t1.mesh).copy_page(pools, 1, 3)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(pools)):
        ax = a.ndim - 4
        np.testing.assert_array_equal(np.take(np.asarray(a), 3, ax),
                                      np.take(np.asarray(b), 1, ax))
    _, res_one, out_one = run(single_host(2))
np.testing.assert_array_equal(res_pod.pred, res_one.pred)
np.testing.assert_array_equal(res_pod.tier_of, res_one.tier_of)
assert out_pod == out_one, (out_pod, out_one)
assert len(out_pod) == 3 and all(len(o) == 4 for _, _, o in out_pod)
print("POD_KERNELS_OK", res_pod.tier_counts.tolist())
"""


def test_pod_placement_runs_kernels_per_device_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", _POD_KERNEL_SCRIPT],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True, text=True, timeout=560,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "POD_KERNELS_OK" in r.stdout
