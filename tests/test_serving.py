"""Serving engine tests (ISSUE 6): paged KV cache + continuous batching.

The load-bearing assertions:
- incremental paged decode is TOKEN-IDENTICAL to the full re-encode
  forward, across batch sizes, block sizes, and early-EOS patterns;
- block reuse (free -> realloc) cannot leak stale KV into a new sequence;
- the steady-state decode loop holds the no-retrace invariant while
  sequences of different lengths join and leave the batch;
- SLA deadlines evict, preemption-by-recompute converges, telemetry SLOs
  populate.

One shared llama engine config keeps the jit-compile count low — the
jitted decode/prefill entries are module-level in serving.models, so
engines with equal config + shapes share executables.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.analysis.runtime import no_retrace
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import llama, transformer
from mxnet_tpu.serving.cache import BlockAllocator, CacheOOMError

EOS = 2
BOS = 1


def _llama_tiny(seed):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = llama.llama_model("llama_tiny", vocab_size=101)
    net.initialize(mx.initializer.Normal(0.05))
    net(mx.nd.array(np.zeros((1, 4), np.int32)))     # finish deferred init
    return net


@pytest.fixture(scope="module")
def llama_net():
    return _llama_tiny(7)


@pytest.fixture(scope="module")
def tf_net():
    mx.random.seed(11)
    np.random.seed(11)
    m = transformer.transformer_model("transformer_test", vocab_size=50,
                                      max_length=32, dropout=0.0)
    m.initialize(mx.initializer.Normal(0.3))
    return m


def _llama_engine(net, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("block_tokens", 4)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_tokens", 16)
    return serving.ServingEngine(net, eos_id=EOS, **kw)


def _ref_greedy_llama(net, prompt, max_new, eos=EOS, pad_to=32):
    """Oracle: full re-encode greedy decode on a fixed (1, pad_to) buffer
    (causality hides the tail — one compiled shape)."""
    assert len(prompt) + max_new <= pad_to
    buf = np.zeros((1, pad_to), np.int32)
    buf[0, :len(prompt)] = prompt
    n, out = len(prompt), []
    for _ in range(max_new):
        logits = net(mx.nd.array(buf)).asnumpy()
        nxt = int(logits[0, n - 1].argmax())
        out.append(nxt)
        if nxt == eos:
            break
        buf[0, n] = nxt
        n += 1
    return out


# -- allocator / cache units (no jax) ---------------------------------------

def test_block_allocator_alloc_free_oom():
    a = BlockAllocator(6)                 # blocks 1..5 usable
    assert a.free_blocks == 5
    got = a.alloc(3)
    assert len(got) == 3 and a.free_blocks == 2
    with pytest.raises(CacheOOMError):
        a.alloc(3)
    a.free(got)
    assert a.free_blocks == 5
    with pytest.raises(MXNetError, match="double free"):
        a.free(got[:1])                   # already on the free list


def test_block_allocator_scratch_reserved():
    a = BlockAllocator(4)
    taken = a.alloc(3)
    assert 0 not in taken                 # scratch never issued
    with pytest.raises(MXNetError, match="invalid block"):
        a.free([0])


def test_paged_cache_admit_release_reuse():
    c = serving.PagedKVCache(max_batch=2, max_blocks_per_seq=4,
                             block_tokens=4, num_blocks=9)
    blocks = c.admit(0, 7)                # ceil(7/4) = 2 blocks
    assert len(blocks) == 2 and c.free_blocks == 6
    c.ctx_len[0] = 7
    c.ensure_capacity(0)                  # pos 7 inside block 1: no alloc
    assert c.free_blocks == 6
    c.ctx_len[0] = 8
    c.ensure_capacity(0)                  # pos 8 opens block 2
    assert c.free_blocks == 5
    freed = c.release(0)
    assert len(freed) == 3 and c.free_blocks == 8
    assert (c.tables[0] == 0).all() and c.ctx_len[0] == 0
    reused = c.admit(1, 4)                # LIFO: the freed block comes back
    assert reused[0] in freed


def test_paged_cache_prefix_share_refcount_evict():
    """Prefix-cache bookkeeping without jax: registration, full-block
    sharing with refcounts, COW pair production, LRU eviction of
    refcount-0 cached blocks under pressure."""
    c = serving.PagedKVCache(max_batch=3, max_blocks_per_seq=4,
                             block_tokens=4, num_blocks=6,
                             prefix_cache=True)
    prompt = list(range(10, 20))          # 10 tokens = 2 full blocks + 2
    c.admit(0, 10, prompt)
    c.register_prefix(0, prompt)
    assert c.prefix_hits == 0             # cold admission
    # a second identical-prefix admission shares the 2 full blocks
    h0 = c.prefix_hit_tokens
    c.admit(1, 10, prompt)
    assert c.prefix_hits == 1 and c.prefix_hit_tokens - h0 == 8
    assert c.tables[1][0] == c.tables[0][0]
    assert c.tables[1][1] == c.tables[0][1]
    # COW: slot 1 about to write inside the SHARED second block
    pairs = c.prepare_write(1, 5)
    assert len(pairs) == 1 and pairs[0][0] == c.tables[0][1]
    assert c.tables[1][1] == pairs[0][1] != c.tables[0][1]
    assert c.cow_copies == 1
    # sole-owner writes need no copy
    assert c.prepare_write(0, 5) == []
    # release both: registered blocks park on the cached LRU, not free
    c.release(0)
    c.release(1)
    assert c.cached_blocks == 2
    # pressure: a big admission evicts cached blocks LRU-first
    c.admit(2, 16)                        # 4 blocks > 3 free
    assert c.evictions == 1 and c.cached_blocks == 1
    c.release(2)
    # the evicted deeper key is gone; the surviving first block still hits
    _blocks, toks = c.match_prefix(prompt)
    assert toks == 4


# -- llama: token identity ---------------------------------------------------

def test_llama_paged_decode_token_identical(llama_net):
    """Mixed-length prompts through the continuous batch == per-request
    full re-encode greedy decode, token for token."""
    eng = _llama_engine(llama_net)
    prompts = [[5, 9, 11], [7, 8, 9, 10, 3, 4], [40, 41], [12] * 9]
    outs = eng.generate(prompts, max_new_tokens=12)
    for p, got in zip(prompts, outs):
        assert got == _ref_greedy_llama(llama_net, p, 12), p


@pytest.mark.parametrize("block_tokens", [2, 8])
def test_llama_block_sizes_token_identical(llama_net, block_tokens):
    eng = _llama_engine(llama_net, block_tokens=block_tokens)
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]
    outs = eng.generate(prompts, max_new_tokens=9)
    for p, got in zip(prompts, outs):
        assert got == _ref_greedy_llama(llama_net, p, 9), p


def test_llama_batch_size_independent(llama_net):
    """The same request decodes identically alone and in a full batch
    (B_max=2 vs 4 engines) — slot count is not observable."""
    p = [6, 28, 3, 17]
    solo = _llama_engine(llama_net, max_batch=2).generate(
        [p], max_new_tokens=10)[0]
    crowd = _llama_engine(llama_net).generate(
        [p, [1, 2, 3], [50] * 7, [30, 31]], max_new_tokens=10)[0]
    assert solo == crowd == _ref_greedy_llama(llama_net, p, 10)


def test_llama_early_eos_and_backfill(llama_net):
    """Sequences that stop early (engineered EOS) free their slots for
    queued requests; every request still matches its oracle."""
    prompts = [[5, 9, 11], [7, 8, 9, 10, 3, 4], [40, 41], [12] * 9,
               [33, 2, 7], [64, 65, 66, 67], [90], [13, 37]]
    refs = [_ref_greedy_llama(llama_net, p, 10, eos=-1) for p in prompts]
    # eos = what request 0 emits 3rd: its row ends early, others vary
    eos = refs[0][2]
    net_refs = [_ref_greedy_llama(llama_net, p, 10, eos=eos)
                for p in prompts]
    eng = serving.ServingEngine(llama_net, eos_id=eos, max_batch=3,
                                block_tokens=4, max_seq=64,
                                prefill_tokens=16)
    outs = eng.generate(prompts, max_new_tokens=10)   # 8 reqs, 3 slots
    assert outs == net_refs
    assert any(o[-1] == eos and len(o) < 10 for o in outs)  # early stop real


def test_llama_block_reuse_no_stale_kv(llama_net):
    """free -> realloc cannot leak stale KV: a request decoded over
    just-freed (never zeroed) blocks matches a fresh-engine decode.
    The LIFO allocator guarantees the probe gets the churned blocks."""
    eng = _llama_engine(llama_net)
    churn = eng.generate([[23, 24, 25, 26, 27, 28], [71, 72, 73]],
                         max_new_tokens=14)
    probe = [44, 45, 46, 47]
    probe_blocks = None
    orig_admit = eng.cache.admit

    def spying_admit(slot, n):
        nonlocal probe_blocks
        probe_blocks = orig_admit(slot, n)
        return probe_blocks

    eng.cache.admit = spying_admit
    reused = eng.generate([probe], max_new_tokens=14)[0]
    fresh = _llama_engine(llama_net).generate([probe],
                                              max_new_tokens=14)[0]
    assert reused == fresh == _ref_greedy_llama(llama_net, probe, 14)
    assert churn and probe_blocks  # pool churned, probe really realloc'd


# -- no-retrace invariant ----------------------------------------------------

def test_no_retrace_mixed_lengths(llama_net):
    """Acceptance: the steady-state decode loop compiles NOTHING while
    sequences of differing lengths join and leave the batch."""
    eng = _llama_engine(llama_net)
    eng.generate([[5, 6, 7], [8, 9, 10, 11, 12]], max_new_tokens=6)  # warm
    prompts = [[1], [2, 3], [4, 5, 6, 7], [9] * 11, [10, 11], [12] * 7]
    with no_retrace():
        outs = eng.generate(prompts, max_new_tokens=9)
    # each request ran to its own end (9 tokens, or the oracle's EOS):
    # a random model may emit EOS early, so lengths are the oracle's
    assert outs == [_ref_greedy_llama(llama_net, p, 9) for p in prompts]


# -- scheduling: deadlines, preemption, async -------------------------------

def test_sla_deadline_evicts(llama_net):
    eng = _llama_engine(llama_net)
    before = telemetry.counter(
        "mxnet_serving_requests_evicted_total").value
    h = eng.submit([5, 6, 7], max_new_tokens=8, deadline_s=1e-9)
    import time
    time.sleep(0.01)
    eng.step()
    with pytest.raises(serving.RequestDeadlineExceeded, match="SLA"):
        h.result(timeout=5)
    after = telemetry.counter("mxnet_serving_requests_evicted_total").value
    assert after == before + 1


def test_reject_oversized(llama_net):
    eng = _llama_engine(llama_net)
    h = eng.submit(list(range(3, 20)), max_new_tokens=4)   # > prefill cap
    with pytest.raises(serving.ServingError, match="cannot fit"):
        h.result(timeout=5)
    h2 = eng.submit([5, 6], max_new_tokens=63)             # > max_seq
    with pytest.raises(serving.ServingError, match="cannot fit"):
        h2.result(timeout=5)


def test_preemption_recompute_converges(llama_net):
    """An oversubscribed pool (too small for both sequences' full length)
    forces preemption; the preempted request re-prefills with
    prompt+generated and still matches its oracle exactly."""
    before = telemetry.counter(
        "mxnet_serving_requests_preempted_total").value
    # eos 255 is never emitted (vocab 101): both sequences must run their
    # full 10 tokens, oversubscribing the 4-block pool (7 blocks demand)
    eng = serving.ServingEngine(llama_net, eos_id=255, max_batch=2,
                                block_tokens=4, max_seq=16,
                                prefill_tokens=16, num_blocks=5)
    prompts = [[5, 9, 11, 13], [7, 8, 9, 10]]
    outs = eng.generate(prompts, max_new_tokens=10)
    for p, got in zip(prompts, outs):
        assert got == _ref_greedy_llama(llama_net, p, 10, eos=-1), p
    after = telemetry.counter(
        "mxnet_serving_requests_preempted_total").value
    assert after > before                 # pressure actually preempted


def test_async_background_thread(llama_net):
    eng = _llama_engine(llama_net)
    eng.start()
    try:
        hs = [eng.submit(p, max_new_tokens=7)
              for p in ([15, 16], [17, 18, 19], [20])]
        results = [h.result(timeout=60) for h in hs]
    finally:
        eng.stop()
    for p, got in zip([[15, 16], [17, 18, 19], [20]], results):
        assert got == _ref_greedy_llama(llama_net, p, 7)


def test_stop_fails_pending_requests(llama_net):
    """stop() must error abandoned handles promptly — not leave callers
    blocked on the full resilience-Deadline timeout."""
    eng = _llama_engine(llama_net)
    h = eng.submit([5, 6, 7], max_new_tokens=8)   # queued, loop never ran
    eng.stop()
    with pytest.raises(serving.ServingError, match="abandoned"):
        h.result(timeout=5)
    assert h.stats()["e2e_s"] is not None         # terminal -> finish_t set
    assert eng.cache.free_blocks == eng.cache.allocator.num_blocks - 1
    late = eng.submit([8, 9], max_new_tokens=4)   # stop() is terminal
    with pytest.raises(serving.ServingError, match="stopped"):
        late.result(timeout=5)


def test_static_policy_matches_tokens(llama_net):
    """policy='static' (the bench baseline) produces the same tokens —
    only the scheduling differs."""
    prompts = [[5, 9, 11], [7, 8, 9], [40, 41], [12, 13], [1, 2, 3]]
    cont = _llama_engine(llama_net).generate(prompts, max_new_tokens=6)
    stat = _llama_engine(llama_net, policy="static").generate(
        prompts, max_new_tokens=6)
    assert cont == stat


# -- engine hardening (ISSUE 13 satellite) ----------------------------------

def test_engine_load_atomic_triple(llama_net):
    """load() returns one consistent (queue_depth, active_slots,
    free_blocks) snapshot under the scheduler lock — the replica-ack /
    least-loaded dispatch signal."""
    eng = _llama_engine(llama_net)
    total_free = eng.cache.allocator.num_blocks - 1
    assert eng.load() == (0, 0, total_free)
    assert eng.free_slots == eng.max_batch
    h = eng.submit([5, 6], max_new_tokens=4)
    assert eng.load() == (1, 0, total_free)      # queued, nothing admitted
    eng.drain()
    assert h.result(timeout=5)
    assert eng.load() == (0, 0, total_free)


def test_submit_blown_deadline_fails_at_submit(llama_net):
    """A non-positive remaining budget (a router forwarding an already
    blown deadline) fails the handle at submit — no queue round-trip,
    no prefill."""
    eng = _llama_engine(llama_net)
    p0 = telemetry.counter("mxnet_serving_prefills_total").value
    h = eng.submit([5, 6, 7], max_new_tokens=4, deadline_s=-0.5)
    assert h.ready()
    with pytest.raises(serving.RequestDeadlineExceeded):
        h.result(timeout=5)
    assert telemetry.counter(
        "mxnet_serving_prefills_total").value == p0


def test_deadline_lapsing_during_admission_skips_prefill(llama_net):
    """A request whose deadline lapses while EARLIER admissions in the
    same scheduler iteration burn prefills is evicted at its own
    admission turn — it must not pay a prefill first."""
    eng = _llama_engine(llama_net)
    orig_prefill = eng.adapter.prefill
    calls = []

    def slow_prefill(slot, prompt, table_row):
        calls.append(slot)
        import time as _t
        _t.sleep(0.08)
        return orig_prefill(slot, prompt, table_row)

    eng.adapter.prefill = slow_prefill
    try:
        ha = eng.submit([5, 6], max_new_tokens=2)           # admits first
        hb = eng.submit([7, 8], max_new_tokens=2, deadline_s=0.03)
        p0 = telemetry.counter("mxnet_serving_prefills_total").value
        eng.step()      # admits A (80ms prefill) -> B's deadline lapses
        with pytest.raises(serving.RequestDeadlineExceeded):
            hb.result(timeout=5)
        assert telemetry.counter(
            "mxnet_serving_prefills_total").value == p0 + 1
        eng.drain()
        assert ha.result(timeout=5)
    finally:
        eng.adapter.prefill = orig_prefill


# -- prefix caching (ISSUE 15 tentpole) --------------------------------------

SYS12 = [30 + i for i in range(12)]       # 3 full blocks at T=4


def test_prefix_cache_hit_token_identical(llama_net):
    """Shared-system-prompt workload: prefix-cache-hit generations are
    bitwise-equal to cold-start, tail-only prefill computes fewer
    positions, and the hit/hit-token telemetry moves."""
    prompts = [SYS12 + [60 + i] for i in range(5)]
    cold = [_ref_greedy_llama(llama_net, p, 8) for p in prompts]
    h0 = telemetry.counter("mxnet_serving_prefix_hits_total").value
    p0 = telemetry.counter("mxnet_serving_prefill_positions_total").value
    eng = _llama_engine(llama_net, prefix_cache=True)
    outs = eng.generate(prompts, max_new_tokens=8)
    assert outs == cold
    assert eng.cache.prefix_hits == 4           # req 0 is the cold fill
    assert eng.cache.prefix_hit_tokens == 4 * len(SYS12)
    assert telemetry.counter(
        "mxnet_serving_prefix_hits_total").value - h0 == 4
    ppos = telemetry.counter(
        "mxnet_serving_prefill_positions_total").value - p0
    # 1 cold padded prefill + 4 one-block tail chunks << 5 cold prefills
    assert ppos == eng.adapter.prefill_tokens + 4 * eng.block_tokens
    assert ppos < 5 * eng.adapter.prefill_tokens


def test_prefix_cow_on_scratch_adjacent_block(llama_net):
    """Two CONCURRENT sequences with the same block-aligned prompt: the
    sharer's boundary chunk must write the last shared block (the one
    adjacent to the scratch-padded table tail) -> copy-on-write fires
    and both outputs stay bitwise-equal to the cold path.  A non-aligned
    duplicate (partial tail block) needs no COW: its tail starts at a
    block boundary in a private block."""
    p8 = [3, 1, 4, 1, 5, 9, 2, 6]               # 2 full blocks exactly
    cold = _ref_greedy_llama(llama_net, p8, 8)
    c0 = telemetry.counter("mxnet_serving_prefix_cow_total").value
    eng = _llama_engine(llama_net, prefix_cache=True)
    outs = eng.generate([p8, list(p8)], max_new_tokens=8)
    assert outs == [cold, cold]
    assert eng.cache.cow_copies >= 1
    assert telemetry.counter(
        "mxnet_serving_prefix_cow_total").value - c0 >= 1
    p9 = p8 + [7]                               # partial third block
    cold9 = _ref_greedy_llama(llama_net, p9, 8)
    eng2 = _llama_engine(llama_net, prefix_cache=True)
    outs2 = eng2.generate([p9, list(p9)], max_new_tokens=8)
    assert outs2 == [cold9, cold9]
    assert eng2.cache.cow_copies == 0 and eng2.cache.prefix_hits == 1


def test_prefix_preemption_of_shared_blocks(llama_net):
    """Cache-pressure corner: preempting a sequence whose blocks are
    SHARED (refcount > 1) frees only its private blocks; the preempted
    request recomputes and every output still matches the cold oracle."""
    before = telemetry.counter(
        "mxnet_serving_requests_preempted_total").value
    sysp = [40 + i for i in range(8)]           # 2 shared full blocks
    prompts = [sysp + [70], sysp + [71]]
    eng = serving.ServingEngine(llama_net, eos_id=255, max_batch=2,
                                block_tokens=4, max_seq=16,
                                prefill_tokens=16, num_blocks=6,
                                prefix_cache=True)
    outs = eng.generate(prompts, max_new_tokens=6)
    for p, got in zip(prompts, outs):
        assert got == _ref_greedy_llama(llama_net, p, 6, eos=-1), p
    after = telemetry.counter(
        "mxnet_serving_requests_preempted_total").value
    assert after > before                        # pressure really preempted
    assert eng.cache.prefix_hits >= 1            # sharing really happened


def test_prefix_eviction_races_readmission(llama_net):
    """Cache-pressure corner: an unrelated admission evicts the cached
    prefix between a request's first run and its resubmission — the
    resubmit takes the cold path and stays token-identical."""
    eng = serving.ServingEngine(llama_net, eos_id=EOS, max_batch=1,
                                block_tokens=4, max_seq=24,
                                prefill_tokens=16, num_blocks=6,
                                prefix_cache=True)
    pa = [5, 6, 7, 8, 9, 10, 11, 12]            # 2 registered full blocks
    ra = eng.generate([pa], max_new_tokens=4)[0]
    assert eng.cache.cached_blocks == 2
    pb = list(range(50, 66))                    # 16 tokens: 4 blocks
    eng.generate([pb], max_new_tokens=4)
    assert eng.cache.evictions >= 1             # the race: prefix evicted
    hits0 = eng.cache.prefix_hits
    rb = eng.generate([pa], max_new_tokens=4)[0]
    assert rb == ra == _ref_greedy_llama(llama_net, pa, 4)
    assert eng.cache.prefix_hits == hits0       # evicted: no hit, cold path


# -- speculative decoding (ISSUE 15 tentpole) --------------------------------

@pytest.fixture(scope="module")
def draft_net():
    """A DIVERGENT draft (same llama_tiny config — the module-level jits
    are shared — different seed): low acceptance, so the target-token
    fallback path is exercised on every few dispatches."""
    return _llama_tiny(23)


def test_spec_decode_token_identical_mixed_batch(llama_net, draft_net):
    """Speculative greedy output is bitwise-equal to plain greedy across
    a mixed-length batch and across batch sizes."""
    prompts = [[5, 9, 11], [7, 8, 9, 10, 3, 4], [40, 41], [12] * 9]
    eng = _llama_engine(llama_net, draft_model=draft_net, spec_k=3)
    outs = eng.generate(prompts, max_new_tokens=12)
    for p, got in zip(prompts, outs):
        assert got == _ref_greedy_llama(llama_net, p, 12), p
    solo = _llama_engine(llama_net, max_batch=2, draft_model=draft_net,
                         spec_k=2).generate([prompts[0]],
                                            max_new_tokens=10)[0]
    assert solo == _ref_greedy_llama(llama_net, prompts[0], 10)


def test_spec_decode_early_eos(llama_net, draft_net):
    """EOS inside an accepted run truncates the emission mid-chunk;
    every sequence still matches its oracle exactly."""
    prompts = [[5, 9, 11], [7, 8, 9, 10, 3, 4], [40, 41], [12] * 9,
               [33, 2, 7], [90]]
    free = [_ref_greedy_llama(llama_net, p, 10, eos=-1) for p in prompts]
    eos = free[0][2]
    refs = [_ref_greedy_llama(llama_net, p, 10, eos=eos) for p in prompts]
    eng = serving.ServingEngine(llama_net, eos_id=eos, max_batch=3,
                                block_tokens=4, max_seq=64,
                                prefill_tokens=16,
                                draft_model=draft_net, spec_k=3)
    outs = eng.generate(prompts, max_new_tokens=10)
    assert outs == refs
    assert any(o[-1] == eos and len(o) < 10 for o in outs)


def test_spec_decode_preemption_token_identical(llama_net, draft_net):
    """Pool pressure with speculation armed: preemption-by-recompute
    still converges bit-identically (the spec chunk reserves multiple
    positions per slot, so pressure bites earlier)."""
    before = telemetry.counter(
        "mxnet_serving_requests_preempted_total").value
    eng = serving.ServingEngine(llama_net, eos_id=255, max_batch=2,
                                block_tokens=4, max_seq=16,
                                prefill_tokens=16, num_blocks=5,
                                draft_model=draft_net, spec_k=2)
    prompts = [[5, 9, 11, 13], [7, 8, 9, 10]]
    outs = eng.generate(prompts, max_new_tokens=10)
    for p, got in zip(prompts, outs):
        assert got == _ref_greedy_llama(llama_net, p, 10, eos=-1), p
    assert telemetry.counter(
        "mxnet_serving_requests_preempted_total").value > before


def test_spec_identical_draft_tokens_per_dispatch(llama_net):
    """An identical-weights draft accepts ~everything: generated tokens
    per target dispatch >= 1.5 (the serve-bench gate's mechanism) and
    the accepted-draft-length histogram populates."""
    telemetry.enable()
    try:
        t0 = telemetry.counter("mxnet_serving_tokens_total").value
        s0 = telemetry.counter("mxnet_serving_decode_steps_total").value
        hist = telemetry.REGISTRY.get("mxnet_serving_accepted_draft_tokens")
        hc0 = hist.count if hist is not None else 0
        eng = _llama_engine(llama_net, draft_model=llama_net, spec_k=3)
        outs = eng.generate([[5, 6, 7], [8, 9]], max_new_tokens=12)
        for p, got in zip([[5, 6, 7], [8, 9]], outs):
            assert got == _ref_greedy_llama(llama_net, p, 12), p
        toks = telemetry.counter("mxnet_serving_tokens_total").value - t0
        steps = telemetry.counter(
            "mxnet_serving_decode_steps_total").value - s0
        assert steps > 0 and toks / steps >= 1.5, (toks, steps)
        hist = telemetry.REGISTRY.get("mxnet_serving_accepted_draft_tokens")
        assert hist.count > hc0
    finally:
        if not telemetry.env_enabled():
            telemetry.disable()


def test_prefix_and_spec_no_retrace(llama_net, draft_net):
    """Acceptance: steady-state serving with BOTH features armed
    compiles nothing — cold prefills, tail chunks, draft steps and
    verify dispatches all hold their fixed shapes."""
    eng = _llama_engine(llama_net, prefix_cache=True,
                        draft_model=draft_net, spec_k=3)
    # warm every executable: cold prefill, a prefix-hit tail chunk,
    # draft steps, and the (B, K) verify
    eng.generate([SYS12 + [77], SYS12 + [78], [1, 2, 3]],
                 max_new_tokens=6)
    with no_retrace():
        outs = eng.generate(
            [SYS12 + [88], SYS12 + [89], [4, 5], [9] * 7],
            max_new_tokens=9)
    cold = [_ref_greedy_llama(llama_net, p, 9)
            for p in [SYS12 + [88], SYS12 + [89], [4, 5], [9] * 7]]
    assert outs == cold


# -- transformer (encoder-decoder) ------------------------------------------

def test_transformer_paged_decode_token_identical(tf_net):
    """Paged incremental MT decode == greedy_decode (the re-encode path)
    for every row, including a padded short source."""
    r = np.random.RandomState(0)
    src = r.randint(3, 50, (3, 8)).astype(np.int32)
    vls = [8, 6, 4]
    ref = transformer.greedy_decode(
        tf_net, mx.nd.array(src), BOS, EOS, max_len=12,
        src_valid_length=mx.nd.array(np.array(vls, np.int32)))
    eng = serving.ServingEngine(tf_net, eos_id=EOS, bos_id=BOS,
                                max_batch=4, block_tokens=4, max_seq=16,
                                prefill_tokens=16)
    outs = eng.generate([list(src[i, :vls[i]]) for i in range(3)],
                        max_new_tokens=11)
    for i, got in enumerate(outs):
        want = list(ref[i, 1:])           # strip BOS
        assert got[:len(want)] == want[:len(got)], (i, got, want)


def test_transformer_rejects_max_seq_past_pos_table(tf_net):
    """max_seq beyond the sinusoid table must error at construction —
    jnp.take would clamp those decode positions and emit wrong tokens."""
    with pytest.raises(MXNetError, match="positional table"):
        serving.ServingEngine(tf_net, eos_id=EOS, bos_id=BOS,
                              max_batch=2, block_tokens=4, max_seq=64,
                              prefill_tokens=16)   # tf_net max_length=32


def test_transformer_no_retrace(tf_net):
    eng = serving.ServingEngine(tf_net, eos_id=EOS, bos_id=BOS,
                                max_batch=4, block_tokens=4, max_seq=16,
                                prefill_tokens=16)
    eng.generate([[5, 6, 7]], max_new_tokens=4)          # warm
    with no_retrace():
        outs = eng.generate([[8, 9], [10, 11, 12, 13], [14]],
                            max_new_tokens=6)
    assert all(len(o) == 6 for o in outs)


# -- encode-once satellite ---------------------------------------------------

def test_encode_once_matches_full_forward(tf_net):
    """encode() + decode_from_memory() == the one-shot hybrid forward —
    the contract that lets greedy/beam decode encode the source once."""
    r = np.random.RandomState(3)
    src = mx.nd.array(r.randint(3, 50, (2, 7)).astype(np.int32))
    tgt = mx.nd.array(r.randint(3, 50, (2, 5)).astype(np.int32))
    vl = mx.nd.array(np.array([7, 4], np.int32))
    full = tf_net(src, tgt, vl).asnumpy()
    mem = tf_net.encode(src, vl)
    two_step = tf_net.decode_from_memory(mem, tgt, vl).asnumpy()
    np.testing.assert_allclose(full, two_step, rtol=1e-5, atol=1e-6)


def test_greedy_decode_counts_one_encoder_pass(tf_net, monkeypatch):
    """greedy_decode must hit the encoder exactly once however many
    tokens it emits."""
    calls = {"n": 0}
    orig = type(tf_net).encode

    def counting(self, *a, **kw):
        calls["n"] += 1
        return orig(self, *a, **kw)

    monkeypatch.setattr(type(tf_net), "encode", counting)
    src = mx.nd.array(np.array([[5, 6, 7, 8]], np.int32))
    out = transformer.greedy_decode(tf_net, src, BOS, EOS, max_len=8)
    assert out.shape[0] == 1 and calls["n"] == 1


# -- telemetry SLOs ----------------------------------------------------------

def test_serving_telemetry_slos(llama_net):
    telemetry.enable()
    try:
        t0 = telemetry.counter("mxnet_serving_tokens_total").value
        s0 = telemetry.counter("mxnet_serving_decode_steps_total").value
        p0 = telemetry.counter(
            "mxnet_serving_token_positions_total").value
        ttft = telemetry.REGISTRY.get("mxnet_serving_ttft_seconds")
        e2e = telemetry.REGISTRY.get("mxnet_serving_e2e_seconds")
        h0, e0 = ttft.count, e2e.count
        eng = _llama_engine(llama_net)
        outs = eng.generate([[5, 6], [7, 8, 9]], max_new_tokens=5)
        n_tokens = sum(len(o) for o in outs)
        assert telemetry.counter(
            "mxnet_serving_tokens_total").value == t0 + n_tokens
        steps = telemetry.counter(
            "mxnet_serving_decode_steps_total").value - s0
        assert steps >= 4                   # 5 new tokens, first via prefill
        positions = telemetry.counter(
            "mxnet_serving_token_positions_total").value - p0
        # 2 prefills at the padded shape + B_max per decode step
        assert positions == 2 * eng.adapter.prefill_tokens \
            + steps * eng.max_batch
        assert ttft.count == h0 + 2 and e2e.count == e0 + 2
        assert telemetry.gauge("mxnet_serving_queue_depth").value == 0
        assert telemetry.gauge("mxnet_serving_active_slots").value == 0
    finally:
        if not telemetry.env_enabled():
            telemetry.disable()


def test_serving_request_span_tree(llama_net):
    """ISSUE 10: every request is a linked async span tree in the trace —
    'b' at submit, 'n' markers at admission/first token, 'e' at finish,
    all keyed by request id; prefill spans and decode-step spans carry
    the rid linkage in their args."""
    telemetry.enable()
    telemetry.clear()
    try:
        eng = _llama_engine(llama_net)
        h1, h2 = (eng.submit(p, max_new_tokens=4) for p in ([5, 6], [7, 8]))
        eng.drain()
        out1, out2 = h1.result(5), h2.result(5)
        assert out1 and out2
        evs = telemetry.get_tracer().events()
        for h in (h1, h2):
            rid = str(h.rid)
            tree = [e for e in evs if e.get("cat") == "serving.request"
                    and e.get("id") == rid]
            phs = [e["ph"] for e in tree]
            assert phs[0] == "b" and phs[-1] == "e"
            marks = {e["name"] for e in tree if e["ph"] == "n"}
            assert {"admitted", "first_token"} <= marks
            end = tree[-1]
            assert end["args"]["tokens"] == len(
                (out1 if h is h1 else out2))
            # the tree threads in timestamp order: queue -> ... -> finish
            ts = [e["ts"] for e in tree]
            assert ts == sorted(ts)
        prefill_rids = {e["args"]["rid"] for e in evs
                        if e.get("name") == "serving.prefill"}
        assert {h1.rid, h2.rid} <= prefill_rids
        decode_rids = set()
        for e in evs:
            if e.get("name") == "serving.decode_step":
                decode_rids.update(e["args"]["rids"])
        assert {h1.rid, h2.rid} <= decode_rids
    finally:
        telemetry.clear()
        if not telemetry.env_enabled():
            telemetry.disable()


# -- the scheduler's exact counts ---------------------------------------------
# Serving has no benchmark cell yet: these counts are all that holds its
# scheduler still.  Each workload runs in a child (tests/_serving_counts_child.py)
# because programs built and compiles depend on what a process compiled before;
# the counters include the child's one warm request (3 prompt tokens, 2 new).

_SCHEDULER_COUNTS = {
    "continuous": {
        "dispatches": 20,
        "compiles_in_workload": 2,
        "counters": {
            "mxnet_serving_decode_steps_total": 15,
            "mxnet_serving_prefill_positions_total": 100,
            "mxnet_serving_prefix_hit_tokens_total": 8,
            "mxnet_serving_prefix_hits_total": 1,
            "mxnet_serving_requests_completed_total": 7,
            "mxnet_serving_token_positions_total": 160,
            "mxnet_serving_tokens_total": 50,
        },
    },
    "spec_decode": {
        "dispatches": 36,
        "compiles_in_workload": 0,
        "counters": {
            "mxnet_serving_accepted_draft_tokens_count": 29,
            "mxnet_serving_accepted_draft_tokens_sum": 0,
            "mxnet_serving_decode_steps_total": 8,
            "mxnet_serving_draft_steps_total": 24,
            "mxnet_serving_prefill_positions_total": 80,
            "mxnet_serving_requests_completed_total": 5,
            "mxnet_serving_token_positions_total": 208,
            "mxnet_serving_tokens_total": 34,
        },
    },
}


@pytest.mark.parametrize("workload", list(_SCHEDULER_COUNTS))
def test_scheduler_counts_are_exact(workload):
    """Dispatches, programs and the scheduler's counters of one workload
    after one warm request, in a fresh process.

    ``continuous`` (6 requests over 4 slots with the prefix cache): 5
    prefills, the tail chunk of the one request whose first 8 tokens hit the
    prefix cache (through ``llama_multi``) and 14 decode steps = 20
    dispatches.  Its 2 compiles inside the workload are both that tail-chunk
    program: the warm request hits no prefix, so the first hit builds it,
    and the armed ledger's memory analysis (``MXNET_COSTMODEL_MEMORY``, on
    by default) compiles it once more; a second pass of the workload
    compiles nothing.  ``spec_decode`` (k = 3, a draft of other weights that
    is never accepted): 4 target + 4 draft prefills, 21 draft steps, 7
    verify steps = 36 dispatches, and no compile: the warm request has
    built all three programs."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = {k: v for k, v in os.environ.items()
           if k != "MXNET_COSTMODEL_MEMORY"}
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(here), here, env.get("PYTHONPATH", "")])
    done = subprocess.run(
        [sys.executable, os.path.join(here, "_serving_counts_child.py"),
         workload], env=env, capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    want = _SCHEDULER_COUNTS[workload]
    assert got["dispatches"] == want["dispatches"]
    assert got["compiles_in_workload"] == want["compiles_in_workload"]
    assert got["executables"] == {"serving.llama_prefill": 1,
                                  "serving.llama_decode": 1,
                                  "serving.llama_multi": 1}
    assert {k: got["counters"][k] for k in want["counters"]} \
        == want["counters"]
