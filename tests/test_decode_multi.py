"""Multi-step decode (lax.scan fused decode blocks): exact equivalence
with per-token stepping, and scheduler block-mode correctness (stop
conditions inside a block, TTFT protection)."""

import uuid

import numpy as np
import pytest

from dynamo_tpu.engine import InferenceScheduler, ModelRunner, RunnerConfig
from dynamo_tpu.llm.protocols import (
    EngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models import get_config
from dynamo_tpu.parallel import MeshConfig, make_mesh


def _runner():
    return ModelRunner(
        get_config("tiny-test"),
        RunnerConfig(page_size=4, num_pages=64, max_batch=4,
                     max_pages_per_seq=16, prefill_buckets=(8, 16, 32)),
        make_mesh(MeshConfig()),
        seed=0,
    )


def _prefill_two(runner, prompt_a, prompt_b):
    tables = np.zeros((4, 16), np.int32)
    tables[0, :8] = np.arange(1, 9)
    tables[1, :8] = np.arange(9, 17)
    runner.prefill_chunk(np.asarray(prompt_a, np.int32), 0, tables[0],
                         len(prompt_a), (0.0, 1.0, 0, 0))
    runner.prefill_chunk(np.asarray(prompt_b, np.int32), 0, tables[1],
                         len(prompt_b), (0.0, 1.0, 0, 0))
    return tables


def _decode_args(prompt_len, temp=0.0, seeds=(0, 0)):
    b = 4
    tokens = np.zeros(b, np.int32)
    tokens[:2] = [5, 7]
    positions = np.zeros(b, np.int32)
    positions[:2] = prompt_len
    kv_lens = np.zeros(b, np.int32)
    kv_lens[:2] = prompt_len + 1
    active = np.zeros(b, bool)
    active[:2] = True
    t = np.zeros(b, np.float32)
    t[:2] = temp
    top_p = np.ones(b, np.float32)
    top_k = np.zeros(b, np.int32)
    sd = np.zeros(b, np.uint32)
    sd[:2] = seeds
    steps = np.zeros(b, np.int32)
    return tokens, positions, kv_lens, active, t, top_p, top_k, sd, steps


def test_forward_decode_matches_unified_forward():
    """The deferred-write decode path (attend over cache + in-register
    current K/V, batched scatter at step end) must produce logits AND
    cache state identical to the unified forward (write-then-attend)."""
    import jax.numpy as jnp

    from dynamo_tpu.models import forward, make_kv_cache
    from dynamo_tpu.models.transformer import forward_decode

    runner = _runner()
    cfg = runner.model_config
    prompt = list(range(1, 7))
    tables = _prefill_two(runner, prompt, list(range(2, 8)))
    (kv0,), _ = runner.cache  # populated by the two prefills

    tokens = np.asarray([5, 7, 0, 0], np.int32)
    positions = np.full(4, len(prompt), np.int32)
    kv_lens = np.full(4, len(prompt) + 1, np.int32)
    active = np.asarray([True, True, False, False])

    kv_a, logits_a = forward(
        runner.params, cfg, jnp.asarray(tokens)[:, None],
        jnp.asarray(positions)[:, None], jnp.asarray(kv0),
        jnp.asarray(tables), jnp.asarray(kv_lens),
        valid=jnp.asarray(active)[:, None])
    kv_b, logits_b = forward_decode(
        runner.params, cfg, jnp.asarray(tokens), jnp.asarray(positions),
        jnp.asarray(kv0), jnp.asarray(tables), jnp.asarray(kv_lens),
        jnp.asarray(active))
    np.testing.assert_allclose(np.asarray(logits_a)[:2],
                               np.asarray(logits_b)[:2],
                               rtol=2e-2, atol=2e-2)
    # the caches agree exactly where real pages were written
    np.testing.assert_array_equal(
        np.asarray(kv_a)[:, :, 1:], np.asarray(kv_b)[:, :, 1:])
    # greedy decision identical
    np.testing.assert_array_equal(
        np.argmax(np.asarray(logits_a)[:2, 0], -1),
        np.argmax(np.asarray(logits_b)[:2, 0], -1))


@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_decode_multi_matches_sequential(temp):
    """K fused steps produce byte-identical tokens to K sequential calls
    (greedy AND seeded sampling — the per-step seed fold-in matches)."""
    prompt = list(range(1, 7))
    k = 4

    r1 = _runner()
    tables = _prefill_two(r1, prompt, list(range(2, 8)))
    tok, pos, lens, act, t, tp, tk, sd, st = _decode_args(len(prompt), temp,
                                                          seeds=(11, 22))
    seq_tokens = []
    for _ in range(k):
        out = r1.decode(tok.copy(), pos.copy(), tables, lens.copy(), act,
                        t, tp, tk, sd, st.copy())
        seq_tokens.append(out[:2].copy())
        tok[:2] = out[:2]
        pos[:2] += 1
        lens[:2] += 1
        st[:2] += 1

    r2 = _runner()
    tables2 = _prefill_two(r2, prompt, list(range(2, 8)))
    tok2, pos2, lens2, act2, t2, tp2, tk2, sd2, st2 = _decode_args(
        len(prompt), temp, seeds=(11, 22))
    toks_k = r2.decode_multi(tok2, pos2, tables2, lens2, act2, t2, tp2,
                             tk2, sd2, st2, k=k)
    assert toks_k.shape[0] == k
    for step in range(k):
        np.testing.assert_array_equal(toks_k[step][:2], seq_tokens[step])


class _Collect:
    def __init__(self):
        self.outputs = []

    def __call__(self, out: EngineOutput):
        self.outputs.append(out)

    def tokens(self):
        return [t for o in self.outputs for t in o.token_ids]

    @property
    def finish(self):
        for o in self.outputs:
            if o.finish_reason:
                return o.finish_reason
        return None


def _run_scheduler(decode_block, max_tokens=9, eos=None, n_requests=1,
                   pipeline=1):
    runner = _runner()
    sched = InferenceScheduler(runner)
    sched.decode_block = decode_block
    sched.decode_pipeline = pipeline
    sched.start()
    collectors = []
    try:
        handles = []
        for i in range(n_requests):
            col = _Collect()
            collectors.append(col)
            req = PreprocessedRequest(
                request_id=uuid.uuid4().hex,
                token_ids=list(range(1 + i, 9 + i)),
                sampling=SamplingOptions(max_tokens=max_tokens,
                                         temperature=0.0),
                stop=StopConditions(ignore_eos=eos is None),
                eos_token_ids=[eos] if eos is not None else [],
            )
            handles.append(sched.submit(req, col))
        import time

        deadline = time.time() + 60
        while (any(c.finish is None for c in collectors)
               and time.time() < deadline):
            time.sleep(0.02)
    finally:
        sched.stop()
    return collectors


def test_scheduler_block_mode_stream_identical():
    base = _run_scheduler(1, n_requests=2)
    blocked = _run_scheduler(4, n_requests=2)
    for c1, c2 in zip(base, blocked):
        assert c1.finish == c2.finish == "length"
        assert c1.tokens() == c2.tokens()


def test_scheduler_block_mode_eos_mid_block():
    """EOS inside a fused block: the stream stops AT the eos token, extra
    block tokens are discarded, and both modes agree exactly."""
    base = _run_scheduler(1, max_tokens=12, eos=None)
    # find what greedy generates, pick the 3rd token as EOS (mid-block for
    # block=4: tokens 1-4 in the first fused block)
    toks = base[0].tokens()
    eos = toks[2]
    first_eos = toks.index(eos)
    b1 = _run_scheduler(1, max_tokens=12, eos=eos)
    b4 = _run_scheduler(4, max_tokens=12, eos=eos)
    assert b1[0].tokens() == b4[0].tokens() == toks[: first_eos + 1]
    assert b1[0].finish == b4[0].finish == "stop"


def test_scheduler_pipelined_blocks_stream_identical():
    """Depth-2 pipelined dispatch (device-chained tokens, speculative
    second block) must produce byte-identical streams to per-token mode."""
    base = _run_scheduler(1, max_tokens=17, n_requests=2)
    piped = _run_scheduler(4, max_tokens=17, n_requests=2, pipeline=2)
    for c1, c2 in zip(base, piped):
        assert c1.finish == c2.finish == "length"
        assert c1.tokens() == c2.tokens()


def test_scheduler_pipelined_eos_mid_first_block():
    """EOS inside block d while block d+1 was already dispatched: the
    speculated tokens must be discarded and the stream match exactly."""
    base = _run_scheduler(1, max_tokens=16, eos=None)
    toks = base[0].tokens()
    eos = toks[2]
    first_eos = toks.index(eos)
    piped = _run_scheduler(4, max_tokens=16, eos=eos, pipeline=2)
    assert piped[0].tokens() == toks[: first_eos + 1]
    assert piped[0].finish == "stop"


def test_scheduler_pipeline_depth_reduced_near_budget():
    """max_tokens < depth*block: the scheduler must degrade to depth 1 /
    block 1 rather than write past the token budget."""
    base = _run_scheduler(1, max_tokens=6, n_requests=1)
    piped = _run_scheduler(4, max_tokens=6, n_requests=1, pipeline=2)
    assert piped[0].tokens() == base[0].tokens()
    assert piped[0].finish == "length"


# -- one frame a sequence a drained block ------------------------------------

_SHARED = {}


def _shared_runner():
    """One runner for the frame tests (its programs compile once); every
    case makes its own scheduler, so its own page pool."""
    if "runner" not in _SHARED:
        _SHARED["runner"] = _runner()
    return _SHARED["runner"]


def _request(prompt, max_tokens, *, eos=None, stop_ids=(), sampled=False):
    """Greedy, or seeded sampling: the toy's greedy stream repeats one
    token, so a stop is placed on a sampled one."""
    return PreprocessedRequest(
        request_id=uuid.uuid4().hex, token_ids=list(prompt),
        sampling=SamplingOptions(max_tokens=max_tokens,
                                 temperature=1.0 if sampled else 0.0,
                                 seed=11 if sampled else None),
        stop=StopConditions(ignore_eos=eos is None,
                            stop_token_ids=list(stop_ids)),
        eos_token_ids=[eos] if eos is not None else [],
    )


def _stepped(requests, *, block, depth=1, spec=False, after_step=None,
             then=None):
    """Drive a scheduler by hand (no thread) until every request has its
    finish; returns the collectors and the scheduler."""
    sched = InferenceScheduler(_shared_runner())
    sched.decode_block = block
    sched.decode_pipeline = depth
    assert sched.spec_enabled == spec
    cols = [_Collect() for _ in requests]
    handles = [sched.submit(r, c) for r, c in zip(requests, cols)]
    for i in range(200):
        sched._drain_control()
        sched._drain_incoming()
        sched._step()
        if i == after_step:
            then(sched, handles, cols)
        if all(c.finish is not None or h.seq.cancelled
               for c, h in zip(cols, handles)):
            break
    return cols, sched


_PROMPT = [3, 9, 3, 9, 3, 9, 3, 9]  # repeats: the n-gram proposer mines it


def _base_tokens(sampled=False, n=20):
    """The per-token path's stream (block 1: a frame a token)."""
    if sampled not in _SHARED:
        cols, _ = _stepped([_request(_PROMPT, n, sampled=sampled)], block=1)
        assert [len(o.token_ids) for o in cols[0].outputs] == [1] * n
        _SHARED[sampled] = cols[0].tokens()
    return _SHARED[sampled]


def _first_seen_at(tokens, lo, hi):
    """An index in [lo, hi) whose token occurs nowhere before it."""
    for i in range(lo, hi):
        if tokens[i] not in tokens[:i]:
            return i
    raise AssertionError(f"no fresh token in {tokens[lo:hi]} of {tokens}")


@pytest.mark.parametrize("where", ["mid_block", "second_chained_block"])
@pytest.mark.parametrize("reason", ["stop_token", "eos", "length"])
def test_a_frame_ends_at_the_finishing_token(reason, where):
    """A finish inside a fused block, or inside the second of two chained
    blocks, closes the frame AT that token with the finish reason; what
    the device computed past it is discarded and no frame follows."""
    base = _base_tokens(sampled=True)
    # generated[0] is prefill's; a block of 8 then holds 1..8, two
    # chained blocks of 4 hold 1..4 and 5..8
    block, depth, lo, hi = ((8, 1, 2, 7) if where == "mid_block"
                            else (4, 2, 5, 8))
    if reason == "length":
        idx = lo + 1
        req = _request(_PROMPT, idx + 1, sampled=True)
    else:
        idx = _first_seen_at(base, lo, hi)
        req = (_request(_PROMPT, 20, eos=base[idx], sampled=True)
               if reason == "eos" else
               _request(_PROMPT, 20, stop_ids=[base[idx]], sampled=True))
    (col,), sched = _stepped([req], block=block, depth=depth)
    frames = [o.token_ids for o in col.outputs]
    assert frames == [base[:1], base[1:idx + 1]]
    assert [o.finish_reason for o in col.outputs] == [
        None, "length" if reason == "length" else "stop"]
    assert col.outputs[0].prompt_tokens == len(_PROMPT)
    assert sched.stats.emit_frames == 2
    assert all(s is None for s in sched._slots)  # reaped, nothing open


def test_a_cancelled_or_finished_sequence_emits_nothing():
    """Three rows in one block: one is cancelled before the drain, one
    finishes at its second token; neither gets a frame for what the block
    computed past that, the third gets its eight."""
    base = _base_tokens()

    def cancel_first(sched, handles, cols):
        # the first token came with this step's finalize_prefill
        assert [c.tokens() for c in cols] == [base[:1]] * 3
        handles[0].cancel()

    reqs = [_request(_PROMPT, 20), _request(_PROMPT, 2),
            _request(_PROMPT, 17)]
    cols, sched = _stepped(reqs, block=8, after_step=1,
                           then=cancel_first)
    assert [o.token_ids for o in cols[0].outputs] == [base[:1]]
    assert cols[0].finish is None
    assert [o.token_ids for o in cols[1].outputs] == [base[:1], base[1:2]]
    assert cols[1].finish == "length"
    assert [o.token_ids for o in cols[2].outputs] == [
        base[:1], base[1:9], base[9:17]]
    assert cols[2].finish == "length"


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("block", [1, 8])
def test_frames_carry_the_per_token_paths_ids(block, depth, spec, monkeypatch):
    """Whatever the block, the chaining and the speculation, the ids a
    client is sent are the per-token path's, token for token (a greedy
    row the n-gram proposer can mine, beside a sampled one), in frames
    of at most block x depth (or the verified run of a spec step)."""
    if spec:
        monkeypatch.setenv("DYNT_SPEC_ENABLE", "1")
    reqs = [_request(_PROMPT, 20), _request(_PROMPT, 13, sampled=True)]
    cols, sched = _stepped(reqs, block=block, depth=depth, spec=spec)
    assert cols[0].tokens() == _base_tokens()
    assert cols[1].tokens() == _base_tokens(sampled=True)[:13]
    assert cols[0].finish == cols[1].finish == "length"
    sizes = [len(o.token_ids) for c in cols for o in c.outputs]
    assert sum(sizes) == 33 and min(sizes) >= 1
    assert sched.stats.emit_frames == len(sizes)
    if spec:
        assert sched.stats.spec_steps > 0 and sched.stats.spec_accepted > 0
    else:
        # a frame is what ONE drain gave, never more: 19 tokens follow
        # the longer row's first
        assert max(sizes) == (min(block * depth, 19) if block > 1 else 1)


def test_an_open_frame_goes_out_before_the_error_that_ends_its_stream():
    """A logits processor that fails inside a speculative step's host
    verification: the tokens the step had verified reach the client
    before the error frame, never behind it."""
    sched = InferenceScheduler(_shared_runner())
    col = _Collect()
    seq = sched._prepare(_request(_PROMPT, 20), col)
    sched._append_token(seq, 7, prompt_tokens=len(_PROMPT))
    sched._append_token(seq, 8)
    assert col.outputs == []  # the frame is open: nothing is out yet
    sched._fail_processor_seq(seq, ValueError("bad token id"))
    sched._end_emit()
    assert [(o.token_ids, o.finish_reason) for o in col.outputs] == [
        ([7, 8], None), ([], "error")]
    assert col.outputs[0].prompt_tokens == len(_PROMPT)
    assert seq.finished and seq.frame is None
