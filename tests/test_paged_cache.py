"""Paged KV cache: release-path invalidation (the regression the dense
path shipped without), paged-vs-dense token identity under staggered
mixed-length admissions (XLA + Pallas interpret), page-pool admission
pressure (deferred admissions are counted, never silent), cross-request
prefix sharing with copy-on-write, the free-page headroom clamp the
SLO scheduler consults before sizing a decode quantum, and the
concurrency paging buys at an equal cache budget."""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import cost_model as cm
from repro.core.qos import DEFAULT_TIERS
from repro.core.scheduler import VeltairPolicy
from repro.kernels import dispatch
from repro.models import build_model
from repro.serving import OnlineRuntime, Workload, build_paper_plans
from repro.serving.engine import Request, ServingEngine
from repro.serving.paging import TRASH_PAGE, PagePool
from repro.serving.slo import AdmissionController, SloEntry, pick_quantum

MAX_LEN = 32
PAGE = 8
N_NEW = 4


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced_config("gemma-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(autouse=True)
def _clean_dispatch():
    yield
    dispatch.set_mode("xla")
    dispatch.clear_tile_overrides()


def _mixed_requests(cfg, n_new=N_NEW):
    """Mixed-length prompts; even-indexed ones share a 10-token prefix."""
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
    reqs = []
    for i, extra in enumerate((3, 5, 7, 2, 9)):
        tail = rng.integers(0, cfg.vocab_size, extra).astype(np.int32)
        p = (np.concatenate([shared, tail]) if i % 2 == 0 else
             rng.integers(0, cfg.vocab_size, 8 + extra).astype(np.int32))
        reqs.append(Request(rid=i, prompt=p, max_new_tokens=n_new))
    return reqs


def _staggered(cfg, params, paged, slots=3, **kw):
    """Admit at different steps so slot reuse and mid-flight joins happen."""
    eng = ServingEngine(cfg, params, batch_slots=slots, max_len=MAX_LEN,
                        page_size=PAGE if paged else None, **kw)
    reqs = _mixed_requests(cfg)
    assert eng.admit_request(reqs[0], drain=True)
    eng.step()                            # slot 0 is a token ahead
    for r in reqs[1:slots]:
        assert eng.admit_request(r, drain=True)
    eng.run_to_completion(reqs[slots:])
    assert all(r.done for r in reqs)
    return {r.rid: list(r.output) for r in reqs}, eng


# ---------------------------------------------------------------------------
# Release-path invalidation — the regression test comes first: a freed
# slot's cache state must be scrubbed AT RELEASE, not merely papered over
# by the next admission's pristine-row prefill.


def test_release_invalidates_freed_rows_dense(setup):
    """Dense regression: after a request completes, every cache leaf must
    be zero again — the previous tenant's KV is unreachable by
    construction, not by hoping the next prefill overwrites it."""
    cfg, _, params = setup
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN)
    rng = np.random.default_rng(3)
    p = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    done = eng.run_to_completion([Request(rid=0, prompt=p,
                                          max_new_tokens=N_NEW)])
    assert done and done[0].done
    for path, leaf in jax.tree_util.tree_leaves_with_path(eng.cache):
        assert not np.any(np.asarray(leaf)), \
            f"released slot leaked state through cache leaf {path}"


def test_release_drops_page_references_paged(setup):
    """Paged counterpart: release is a refcount decrement — after all
    requests finish the pool must fully drain (no leaked pages, no
    dangling commitment) and the slot's table row parks on the trash
    page."""
    cfg, _, params = setup
    _, eng = _staggered(cfg, params, paged=True)
    assert eng.pool.used_pages == 0, eng.page_stats
    assert eng.pool.committed == 0, eng.page_stats
    assert np.all(eng._page_table == TRASH_PAGE)
    eng._sync_table()                     # device table syncs lazily
    assert np.all(np.asarray(eng.cache["page_table"]) == TRASH_PAGE)


# ---------------------------------------------------------------------------
# Token identity: the paged gather/scatter decode and prefill paths must
# reproduce the dense engine bit-for-bit.


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_paged_matches_dense_staggered(setup, mode):
    cfg, _, params = setup
    dispatch.set_mode(mode)
    want, de = _staggered(cfg, params, paged=False)
    got, pe = _staggered(cfg, params, paged=True)
    assert got == want, (mode, got, want)
    assert pe.peak_cache_tokens > 0
    assert pe.cache_utilization > 0
    # paged residency never exceeds the dense footprint at equal slots
    assert pe.pool.peak_used * PAGE <= de.slots * de.max_len


def test_prompt_of_exactly_max_len_minus_one(setup):
    """Boundary: a max_len-1 prompt decodes exactly one token (the last
    cache position) then finishes on the length clamp — identically on
    both paths, with the paged run touching its final page."""
    cfg, _, params = setup
    rng = np.random.default_rng(5)
    p = rng.integers(0, cfg.vocab_size, MAX_LEN - 1).astype(np.int32)
    outs = {}
    for paged in (False, True):
        eng = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN,
                            page_size=PAGE if paged else None)
        req = Request(rid=0, prompt=p, max_new_tokens=N_NEW)
        eng.run_to_completion([req])
        assert req.done
        outs[paged] = list(req.output)
        if paged:
            assert eng.pool.used_pages == 0, eng.page_stats
    assert outs[True] == outs[False]
    assert len(outs[True]) == 2           # prefill token + one decode step


def test_slot_reuse_after_page_pool_deferral(setup):
    """A request refused on page-pool exhaustion (counted as a conflict)
    must admit cleanly once the resident request frees its pages — and
    the reused pages must not leak the previous tenant's KV."""
    cfg, _, params = setup
    rng = np.random.default_rng(13)
    a = rng.integers(0, cfg.vocab_size, 17).astype(np.int32)
    b = rng.integers(0, cfg.vocab_size, 17).astype(np.int32)

    def solo(p):
        eng = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN)
        req = Request(rid=0, prompt=p, max_new_tokens=N_NEW)
        eng.run_to_completion([req])
        return list(req.output)

    want_a, want_b = solo(a), solo(b)
    # pool sized so A's worst-case commitment starves B despite slot 1
    # being free: ceil((17+4)/8)=3 pages committed of 4 total
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                        page_size=PAGE, n_pages=4)
    ra = Request(rid=0, prompt=a, max_new_tokens=N_NEW)
    rb = Request(rid=1, prompt=b, max_new_tokens=N_NEW)
    assert eng.admit_request(ra, drain=True)
    needed, free = eng.admission_pages(b, N_NEW)
    assert free is not None and needed > free
    assert not eng.admit_request(rb, drain=True)   # deferred, counted
    assert eng.page_stats["conflicts"] >= 1
    eng.run_to_completion([rb])                    # admits after A frees
    assert ra.done and rb.done
    assert list(ra.output) == want_a
    assert list(rb.output) == want_b
    assert eng.pool.used_pages == 0 and eng.pool.committed == 0


def test_prefix_sharing_and_copy_on_write(setup):
    """Staggered arrivals against a resident request: a full-page prefix
    share, a partial-tail borrow, and the borrower's first decode write
    privatizing the shared page — all token-identical to dense."""
    cfg, _, params = setup

    def run(paged):
        rng = np.random.default_rng(11)
        base = rng.integers(0, cfg.vocab_size, 17).astype(np.int32)
        r0 = Request(rid=0, prompt=base, max_new_tokens=6)
        r1 = Request(rid=1, prompt=np.concatenate(
            [base[:10],
             rng.integers(0, cfg.vocab_size, 4).astype(np.int32)]),
            max_new_tokens=6)
        r2 = Request(rid=2, prompt=base[:12].copy(), max_new_tokens=6)
        eng = ServingEngine(cfg, params, batch_slots=3, max_len=MAX_LEN,
                            page_size=PAGE if paged else None)
        assert eng.admit_request(r0, drain=True)
        eng.step_quantum(2)               # r0 publishes its prompt pages
        assert eng.admit_request(r1, drain=True)
        assert eng.admit_request(r2, drain=True)
        eng.run_to_completion([])
        return {r.rid: list(r.output) for r in (r0, r1, r2)}, eng

    want, _ = run(False)
    got, pe = run(True)
    assert got == want, (got, want)
    st = pe.page_stats
    assert st["shared_hits"] >= 2, st     # r1 full page + r2 partial tail
    assert st["cow_copies"] >= 1, st      # r2's decode privatized its page
    assert pe.pool.used_pages == 0 and pe.pool.committed == 0


# ---------------------------------------------------------------------------
# Memory as a scheduling dimension.


def test_decode_k_headroom_clamps_quantum(setup):
    """With one free page a 16-step quantum would cross two page
    boundaries; the engine must clamp to the 8 steps the pool can map."""
    cfg, _, params = setup
    eng = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN,
                        page_size=PAGE, n_pages=2, page_reserve="prompt")
    rng = np.random.default_rng(17)
    p = rng.integers(0, cfg.vocab_size, PAGE).astype(np.int32)
    assert eng.admit_request(Request(rid=0, prompt=p, max_new_tokens=16),
                             drain=True)
    assert eng.pool.free_pages == 1
    assert eng.decode_k_headroom(16) == 8
    assert eng.decode_k_headroom(4) == 4          # within one page
    dense = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN)
    assert dense.decode_k_headroom(16) == 16      # dense: passthrough


def test_pick_quantum_consults_page_headroom():
    """The EDF scheduler clamps decode quanta through the engine's
    headroom hook when present, and passes k through otherwise."""

    class _Book:
        def get(self, rid):
            return None

    class _Paged:
        def prefill_queue(self):
            return []

        def decode_backlog(self):
            return [(0, 0, 5)]

        def decode_k_headroom(self, k):
            return min(k, 3)

    class _Dense(_Paged):
        decode_k_headroom = None          # not callable -> no clamp

    assert pick_quantum(_Paged(), _Book(), 0.0, 1e-3, 16) == ("decode", 3)
    assert pick_quantum(_Dense(), _Book(), 0.0, 1e-3, 16) == ("decode", 16)


def test_admission_controller_defers_on_page_shortage():
    """Page-pool exhaustion is an admission dimension: a worst-case
    commitment larger than the uncommitted surplus defers even with a
    slot free; dense engines (pages_free=None) skip the gate."""
    ac = AdmissionController()
    spec = DEFAULT_TIERS["standard"]
    entry = SloEntry(rid=0, tenant="t", tier="standard", arrival=0.0,
                     qos_s=1.0, deadline=2.5, ttft_deadline=1.5)
    kw = dict(now=0.0, entry=entry, spec=spec, step_dt=1e-3, own_chunks=1,
              own_decode_steps=4, backlog_chunks=0, slot_free=True)
    assert ac.decide(**kw, pages_needed=3, pages_free=2) == "defer"
    assert ac.decide(**kw, pages_needed=2, pages_free=2) == "admit"
    assert ac.decide(**kw, pages_needed=3, pages_free=None) == "admit"


def test_page_pool_refcounts_and_commitment():
    """PagePool invariants without a device: reserved allocations can
    never fail, unreserved allocations respect outstanding commitment,
    publish/lookup share refcounted pages, release drains."""
    pool = PagePool(4, 8)
    assert pool.commit(3)
    assert not pool.commit(2)             # over-commit refused, counted
    assert pool.conflicts == 1
    owned = [pool.alloc(reserved=True) for _ in range(3)]
    assert all(p is not None and p != TRASH_PAGE for p in owned)
    assert pool.committed == 0
    assert pool.alloc(reserved=False) is not None   # last truly-free page
    assert pool.alloc(reserved=False) is None       # empty -> stall counted
    assert pool.stalls == 1
    chain, toks = (), tuple(range(8))
    pool.publish(chain, toks, owned[0])
    assert pool.lookup(chain, toks) == owned[0]
    assert pool.lookup_covering(chain, toks[:5]) == owned[0]
    pool.retain(owned[0])                 # a second request maps the page
    assert pool.refcount(owned[0]) == 2
    pool.release(owned[0])
    assert pool.lookup(chain, toks) == owned[0]     # survives: holder left
    pool.release(owned[0])
    assert pool.lookup(chain, toks) is None         # refcount 0 unpublishes
    assert pool.free_pages == 1


def test_paged_concurrency_at_equal_cache_budget(setup):
    """Memory as a scheduling dimension: a dense engine pins max_len
    positions per slot, so a 64-token cache budget holds 2 rows however
    short they are; a paged engine draws 8-token pages from the same 64
    tokens on demand and commits only each request's worst case, so the
    same bursty traffic runs at least 1.5x as many rows at once, with
    the same tokens (virtual time: a count, not a speed)."""
    cfg, _, params = setup
    hw = cm.CPU_3990X
    tenants = ["resnet50", "googlenet"]
    plans = build_paper_plans(tenants, hw)
    budget = 2 * MAX_LEN
    wl = Workload.bursty(tenants, 400.0, 20, prompt_len=8, max_new_tokens=3,
                         seed=5, prompt_len_spread=3, shared_prefix_len=PAGE)
    peak, resident, outputs = {}, {}, {}
    for name, kw in (("dense", dict(batch_slots=budget // MAX_LEN)),
                     ("paged", dict(batch_slots=6, page_size=PAGE,
                                    n_pages=budget // PAGE))):
        eng = ServingEngine(cfg, params, max_len=MAX_LEN, **kw)
        runtime = OnlineRuntime(eng, VeltairPolicy(hw), plans, hw)
        m = runtime.serve(wl)
        assert m.n_queries == wl.n_queries
        peak[name] = eng.peak_active_slots
        resident[name] = (eng.pool.peak_used * PAGE if eng.paged
                          else eng.slots * MAX_LEN)
        outputs[name] = runtime.outputs
    assert all(r <= budget for r in resident.values()), resident
    assert peak["paged"] >= 1.5 * peak["dense"], peak
    assert outputs["paged"] == outputs["dense"]
