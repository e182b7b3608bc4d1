"""Preprocessor + detokenizer + delta generation tests (ref contract:
lib/llm/src/preprocessor.rs lowering, backend.rs incremental detok,
chat_completions stop-string jail)."""

import pytest

from dynamo_tpu.llm import (
    ByteTokenizer,
    DeltaGenerator,
    EngineOutput,
    IncrementalDetokenizer,
    ModelDeploymentCard,
    OpenAIPreprocessor,
    RequestError,
)


def _card(**kwargs):
    return ModelDeploymentCard(name="test-model", context_length=1024, **kwargs)


class TestByteTokenizer:
    def test_roundtrip(self):
        tok = ByteTokenizer()
        text = "hello, wörld! 你好"
        assert tok.decode(tok.encode(text)) == text

    def test_specials(self):
        tok = ByteTokenizer()
        assert tok.decode([104, 105, ByteTokenizer.EOS]) == "hi</s>"


class TestIncrementalDetokenizer:
    def test_streams_stable_text(self):
        tok = ByteTokenizer()
        detok = IncrementalDetokenizer(tok, window=2)
        text = "streaming works"
        ids = tok.encode(text)
        out = ""
        for i in ids:
            out += detok.push([i])
        out += detok.flush()
        assert out == text

    def test_multibyte_unicode_never_split(self):
        tok = ByteTokenizer()
        detok = IncrementalDetokenizer(tok, window=1)
        ids = tok.encode("日本語テスト")
        chunks = [detok.push([i]) for i in ids]
        chunks.append(detok.flush())
        assert "".join(chunks) == "日本語テスト"
        for chunk in chunks:
            assert "�" not in chunk


class _ParentDetokenizer:
    """The incremental detokeniser as it stood before PR 45, kept as the
    oracle: it re-decodes `ids[_ctx_start:stable]` on every push and
    slides the anchor only once the span passes 256 tokens. One line is
    not its own: `sends_twice`, set where a slide strips more "\ufffd"
    than were held back. A byte-fallback decoder turns a whole run of
    byte tokens to "\ufffd" while the run's last character is partial,
    characters already emitted among them; stripping those forgets that
    they went out, and the next piece repeats them."""

    _CTX_KEEP = 16
    _CTX_MAX = 256

    def __init__(self, tokenizer, window=None):
        self._tok = tokenizer
        self._ids = []
        self._window = tokenizer.stable_window if window is None else window
        self._ctx_start = 0
        self._stable_tokens = 0
        self._prev_len = 0
        self.sends_twice = False

    def push(self, token_ids):
        self._ids.extend(token_ids)
        n = len(self._ids)
        stable = n if self._window == 0 else max(0, n - self._window)
        if stable <= self._stable_tokens:
            return ""
        text = self._tok.decode(self._ids[self._ctx_start:stable])
        candidate = text[self._prev_len:]
        while candidate.endswith("\ufffd"):
            candidate = candidate[:-1]
        self._stable_tokens = stable
        self._prev_len += len(candidate)
        if stable - self._ctx_start > self._CTX_MAX:
            held = len(text) - self._prev_len
            self._ctx_start = max(0, stable - self._CTX_KEEP)
            anchored = self._tok.decode(self._ids[self._ctx_start:stable])
            full = len(anchored)
            while anchored.endswith("\ufffd"):
                anchored = anchored[:-1]
            self._prev_len = len(anchored)
            self.sends_twice = full - len(anchored) > held
        return candidate

    def flush(self):
        full = self._tok.decode(self._ids[self._ctx_start:])
        out = full[self._prev_len:]
        self._prev_len = len(full)
        self._stable_tokens = len(self._ids)
        return out


_WORDS = ("the quick brown fox jumps over the lazy dog hello world again "
          "and streaming tokens one by one is thing na\u00efve caf\u00e9 "
          "w\u00f6rld \u4f60\u597d \u65e5\u672c\u8a9e \U0001f642 "
          "\u00e9t\u00e9 a b c , . !").split()


@pytest.fixture(scope="module")
def merge_and_space_tokenizer(tmp_path_factory):
    """A small sentencepiece-style BPE built here (no download): learned
    merges, `\u2581` leading-space pieces of which decode strips the
    first, byte fallback for what the vocabulary lacks (so a character's
    UTF-8 bytes arrive as several tokens), specials that decode to
    nothing. Loaded through HfTokenizer, as a deployment's is."""
    import json

    from tokenizers import (Tokenizer, decoders, models, normalizers,
                            pre_tokenizers, trainers)

    from dynamo_tpu.llm.tokenizer import HfTokenizer

    norm = normalizers.Sequence([normalizers.Prepend("\u2581"),
                                 normalizers.Replace(" ", "\u2581")])
    split = pre_tokenizers.Split("\u2581", "merged_with_next")
    seed = Tokenizer(models.BPE(unk_token="<unk>"))
    seed.normalizer, seed.pre_tokenizer = norm, split
    corpus = [" ".join(w for w in _WORDS if w.isascii())] * 20
    seed.train_from_iterator(corpus, trainers.BpeTrainer(
        vocab_size=120, special_tokens=["<unk>", "<s>", "</s>"],
        show_progress=False))
    model = json.loads(seed.to_str())["model"]
    vocab = dict(model["vocab"])
    for byte in range(256):
        vocab[f"<0x{byte:02X}>"] = len(vocab)
    merges = [tuple(m) if isinstance(m, list) else tuple(m.split(" "))
              for m in model["merges"]]
    tok = Tokenizer(models.BPE(vocab, merges, unk_token="<unk>",
                               byte_fallback=True))
    tok.normalizer, tok.pre_tokenizer = norm, split
    tok.decoder = decoders.Sequence([
        decoders.Replace("\u2581", " "), decoders.ByteFallback(),
        decoders.Fuse(), decoders.Strip(" ", 1, 0)])
    tok.add_special_tokens(["<unk>", "<s>", "</s>"])
    path = tmp_path_factory.mktemp("bpe") / "tokenizer.json"
    tok.save(str(path))
    return HfTokenizer(str(path))


def _id_sequence(kind, tok, length, seed):
    """`length` ids, seeded: running text whose multi-byte characters
    come as one token a byte, specials in between, and stray bytes that
    are no UTF-8 at all (runs of one to three)."""
    import random

    rng = random.Random(f"{kind}/{length}/{seed}")
    if kind == "byte":
        specials = [ByteTokenizer.BOS, ByteTokenizer.EOS,
                    ByteTokenizer.IM_START, ByteTokenizer.IM_END, 300, 511]
        stray = list(range(0x80, 0x100))
    else:
        vocab = tok._tok.get_vocab()
        specials = [vocab["<s>"], vocab["</s>"], vocab["<unk>"]]
        # the library's byte-fallback decoder gives up on a whole run of
        # byte tokens where any of them is no UTF-8, back to characters it
        # had decoded: a stray run stands between two words here and holds
        # no byte that could start a character
        stray = [vocab[f"<0x{b:02X}>"]
                 for b in (*range(0x80, 0xC0), *range(0xF8, 0x100))]
        fence = tok.encode("the")
    ids = []
    while len(ids) < length:
        roll = rng.random()
        if roll < 0.08:
            ids.append(rng.choice(specials))
        elif roll < 0.12:
            run = [rng.choice(stray) for _ in range(rng.randint(1, 3))]
            ids.extend(run if kind == "byte" else fence + run + fence)
        else:
            ids.extend(tok.encode(" ".join(
                rng.choice(_WORDS) for _ in range(rng.randint(1, 6)))))
    return ids[:length]


class TestDetokenizerDecodesWhatIsNew:
    """PR 45: a push decodes a bounded tail, and what it emits is what
    the detokeniser before it emitted, piece for piece; but for the
    characters that one sent twice (`_ParentDetokenizer.sends_twice`:
    the anchor now moves every few tokens, so the count of what is held
    back is carried over it, where stripping went wrong once in some
    thousand tokens of such text)."""

    @pytest.mark.parametrize("piece", [1, 8, 11])
    @pytest.mark.parametrize("length", [1, 5, 40, 700, 3000])
    @pytest.mark.parametrize("kind", ["byte", "bpe"])
    def test_pieces_equal_the_parents_and_a_push_decodes_a_bounded_tail(
            self, kind, length, piece, merge_and_space_tokenizer):
        tok = ByteTokenizer() if kind == "byte" else merge_and_space_tokenizer
        assert tok.stable_window == (0 if kind == "byte" else 4)
        for seed in range(3):
            ids = _id_sequence(kind, tok, length, seed)
            detok, oracle = IncrementalDetokenizer(tok), _ParentDetokenizer(tok)
            # a push: at most the context, the window and the pushed ids
            # in its decode, and the context once more where the anchor
            # is re-set; never a span that grows with the answer
            bound = 2 * detok._CTX_KEEP + tok.stable_window + piece
            pieces, repeated = [], 0
            for at in range(0, len(ids), piece):
                before = detok.decoded_tokens
                pieces.append(detok.push(ids[at:at + piece]))
                parents = oracle.push(ids[at:at + piece])
                if oracle.sends_twice and parents != pieces[-1]:
                    assert parents.endswith(pieces[-1]), at
                    repeated += len(parents) - len(pieces[-1])
                    oracle.sends_twice = False
                else:
                    assert pieces[-1] == parents, at
                assert detok.decoded_tokens - before <= bound, at
            pieces.append(detok.flush())
            assert pieces[-1] == oracle.flush()
            assert repeated <= 3  # characters, in 3,000 tokens
            assert "".join(pieces) == tok.decode(ids)
            assert detok.pushed_tokens == len(ids)
            if kind == "byte" and length >= 700:
                # prefix-stable: the pushed ids and the bytes held back
                assert detok.decoded_tokens < 2 * len(ids)
            elif length >= 700:
                assert detok.decoded_tokens < 24 * len(ids)


class TestPreprocessor:
    def test_chat_template_applied(self):
        pre = OpenAIPreprocessor(_card())
        req = pre.preprocess_chat({
            "model": "test-model",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 10,
        })
        text = pre.tokenizer.decode(req.token_ids)
        assert "<|im_start|>user\nhi<|im_end|>" in text
        assert text.endswith("<|im_start|>assistant\n")
        assert req.sampling.max_tokens == 10

    def test_missing_messages_rejected(self):
        pre = OpenAIPreprocessor(_card())
        with pytest.raises(RequestError):
            pre.preprocess_chat({"model": "m"})

    def test_context_overflow_rejected(self):
        pre = OpenAIPreprocessor(_card())
        with pytest.raises(RequestError):
            pre.preprocess_completions({"prompt": "x" * 5000})

    def test_max_tokens_clamped_to_context(self):
        pre = OpenAIPreprocessor(_card())
        req = pre.preprocess_completions({"prompt": "hello", "max_tokens": 999999})
        assert len(req.token_ids) + req.sampling.max_tokens <= 1024

    def test_token_prompt(self):
        pre = OpenAIPreprocessor(_card())
        req = pre.preprocess_completions({"prompt": [72, 105], "max_tokens": 4})
        assert req.token_ids == [72, 105]

    def test_stop_strings_collected(self):
        pre = OpenAIPreprocessor(_card())
        req = pre.preprocess_completions(
            {"prompt": "x", "stop": ["END", "##"], "max_tokens": 5})
        assert req.stop.stop_strings == ["END", "##"]

    def test_multimodal_text_parts_joined(self):
        pre = OpenAIPreprocessor(_card())
        req = pre.preprocess_chat({
            "messages": [{"role": "user", "content": [
                {"type": "text", "text": "a"}, {"type": "text", "text": "b"},
            ]}],
            "max_tokens": 4,
        })
        assert "ab" in pre.tokenizer.decode(req.token_ids)


class TestDeltaGenerator:
    def _gen(self, stop=None):
        pre = OpenAIPreprocessor(_card())
        req = pre.preprocess_chat({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 32, "stop": stop,
        })
        return DeltaGenerator(pre, req, kind="chat"), pre

    def test_streaming_chunks(self):
        gen, pre = self._gen()
        ids = pre.tokenizer.encode("hello world")
        chunks = []
        for i, tid in enumerate(ids):
            final = i == len(ids) - 1
            out = EngineOutput(token_ids=[tid],
                               finish_reason="stop" if final else None)
            chunks.extend(gen.on_output(out))
        text = "".join(
            c["choices"][0]["delta"].get("content", "") for c in chunks)
        assert text == "hello world"
        assert chunks[0]["choices"][0]["delta"]["role"] == "assistant"
        assert chunks[-1]["choices"][0]["finish_reason"] == "stop"
        assert gen.usage()["completion_tokens"] == len(ids)

    def test_stop_string_truncates(self):
        gen, pre = self._gen(stop=["END"])
        ids = pre.tokenizer.encode("abcENDxyz")
        chunks = []
        for tid in ids:
            chunks.extend(gen.on_output(EngineOutput(token_ids=[tid])))
        # flush any jailed text via a final
        chunks.extend(gen.on_output(EngineOutput(token_ids=[],
                                                 finish_reason="length")))
        text = "".join(
            c["choices"][0]["delta"].get("content", "") for c in chunks)
        assert text == "abc"
        assert gen.finish_reason == "stop"

    def test_stop_prefix_jailed_not_leaked(self):
        gen, pre = self._gen(stop=["ENDSTOP"])
        # Send 'EN' then nothing else: the possible stop prefix is held until
        # the stream finishes, then released since no stop occurred.
        ids = pre.tokenizer.encode("xEN")
        chunks = []
        for tid in ids:
            chunks.extend(gen.on_output(EngineOutput(token_ids=[tid])))
        mid_text = "".join(
            c["choices"][0]["delta"].get("content", "") for c in chunks)
        assert mid_text == "x"
        chunks = gen.on_output(EngineOutput(token_ids=[], finish_reason="stop"))
        tail = "".join(
            c["choices"][0]["delta"].get("content", "") for c in chunks)
        assert tail == "EN"

    def test_final_response_aggregates(self):
        gen, pre = self._gen()
        for tid in pre.tokenizer.encode("done"):
            gen.on_output(EngineOutput(token_ids=[tid]))
        gen.on_output(EngineOutput(token_ids=[], finish_reason="stop"))
        resp = gen.final_response()
        assert resp["choices"][0]["message"]["content"] == "done"
        assert resp["object"] == "chat.completion"


class TestFramesOfSeveralTokens:
    """A decode frame holds what one drain gave a sequence (up to block
    x depth tokens). A client must get, byte for byte, the SSE chunks
    and the `usage` that as many one-token frames give."""

    SCENARIOS = {
        # text, stop strings, finish, eos appended, logprobs
        "plain": ("hello world, again", None, "length", False, False),
        "multibyte_split": ("wörld 你好 ok", None, "length", False, False),
        # frames of 4 behind the first token: a|bcEN|Dxyz -> "END" spans two
        "stop_string_spans_two_frames": ("abcENDxyzw", ["END"], "length",
                                         False, False),
        "stop_prefix_released_at_the_end": ("abcEN", ["END"], "length",
                                            False, False),
        "trimmed_eos": ("bye now", None, "stop", True, False),
        "logprobs": ("hi there", None, "length", False, True),
        "logprobs_trimmed_eos": ("hi all", None, "stop", True, True),
    }

    def _gen(self, kind, stop, logprobs):
        pre = OpenAIPreprocessor(_card())
        body = {"max_tokens": 64, "stop": stop}
        if logprobs:
            body.update({"logprobs": True, "top_logprobs": 2}
                        if kind == "chat" else {"logprobs": 2})
        if kind == "chat":
            req = pre.preprocess_chat(
                {"messages": [{"role": "user", "content": "hi"}], **body})
        else:
            req = pre.preprocess_completions({"prompt": "hi", **body})
        req.eos_token_ids = [ByteTokenizer.EOS]
        gen = DeltaGenerator(pre, req, kind=kind)
        gen.chunk_id, gen.created = "chatcmpl-fixed", 1
        return gen, pre

    @staticmethod
    def _frames(ids, sizes, finish, logprobs, prompt_tokens):
        """The token stream cut into frames of `sizes` (the last repeats),
        as the scheduler builds them: `prompt_tokens` on the first, the
        finish on the last, logprob entries one a token."""
        frames, at = [], 0
        sizes = list(sizes)
        while at < len(ids):
            n = sizes.pop(0) if len(sizes) > 1 else sizes[0]
            part = ids[at:at + n]
            frames.append(EngineOutput(
                token_ids=part,
                prompt_tokens=prompt_tokens if at == 0 else None,
                logprobs=([-0.25 * (at + j + 1) for j in range(len(part))]
                          if logprobs else None),
                top_logprobs=([[[t, -0.25 * (at + j + 1)], [65 + (at + j) % 20, -3.0]]
                               for j, t in enumerate(part)]
                              if logprobs else None)))
            at += n
        frames[-1].finish_reason = finish
        return frames

    @pytest.mark.parametrize("kind", ["chat", "completions"])
    @pytest.mark.parametrize("scenario", sorted(SCENARIOS))
    def test_a_frame_of_k_streams_as_k_frames_of_one(self, scenario, kind):
        import json

        text, stop, finish, eos, logprobs = self.SCENARIOS[scenario]
        streams = {}
        for name, sizes in (("one", [1]), ("block", [1, 4]),
                            ("chained", [1, 8]), ("whole", [64])):
            gen, pre = self._gen(kind, stop, logprobs)
            ids = pre.tokenizer.encode(text)
            if eos:
                ids = ids + [ByteTokenizer.EOS]
            chunks = []
            for frame in self._frames(ids, sizes, finish, logprobs,
                                      len(gen.request.token_ids)):
                chunks.extend(gen.on_output(frame))
            streams[name] = (
                [f"data: {json.dumps(c)}\n\n".encode() for c in chunks],
                gen.usage(), json.dumps(gen.final_response()))
        sse, usage, final = streams["one"]
        assert len(sse) >= 2 and usage["completion_tokens"] > 0
        for name in ("block", "chained", "whole"):
            assert streams[name][0] == sse, name  # every SSE chunk, in order
            assert streams[name][1] == usage, name
            assert streams[name][2] == final, name
        if logprobs:
            key = "content" if kind == "chat" else "tokens"
            per_chunk = [len(json.loads(c[6:])["choices"][0]["logprobs"][key])
                         for c in sse if b'"logprobs"' in c]
            assert set(per_chunk) == {1}  # an entry rides its token's chunk


class TestPriorityWireSurface:
    """Multi-tenant QoS wire surface (docs/multi-tenancy.md): the
    `priority` / `tenant` body fields normalize onto
    PreprocessedRequest; invalid classes 400 at the edge."""

    def _pre(self):
        from dynamo_tpu.llm.model_card import ModelDeploymentCard
        from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor

        return OpenAIPreprocessor(ModelDeploymentCard(name="t"))

    def test_priority_defaults_to_standard(self):
        pre = self._pre().preprocess_chat(
            {"messages": [{"role": "user", "content": "hi"}]})
        assert pre.priority == "standard"
        assert pre.tenant == ""

    def test_priority_and_tenant_normalized(self):
        pre = self._pre().preprocess_chat({
            "messages": [{"role": "user", "content": "hi"}],
            "priority": "  Interactive ", "tenant": "acme"})
        assert pre.priority == "interactive"
        assert pre.tenant == "acme"

    def test_completions_accept_priority(self):
        pre = self._pre().preprocess_completions(
            {"prompt": "hello", "priority": "batch"})
        assert pre.priority == "batch"

    def test_unknown_priority_is_400(self):
        from dynamo_tpu.llm.preprocessor import RequestError

        with pytest.raises(RequestError, match="priority"):
            self._pre().preprocess_chat({
                "messages": [{"role": "user", "content": "hi"}],
                "priority": "urgent"})

    def test_wire_roundtrip_default_omits_fields(self):
        from dynamo_tpu.llm.protocols import PreprocessedRequest

        pre = self._pre().preprocess_chat(
            {"messages": [{"role": "user", "content": "hi"}]})
        wire = pre.to_wire()
        assert "priority" not in wire and "tenant" not in wire
        tagged = self._pre().preprocess_chat({
            "messages": [{"role": "user", "content": "hi"}],
            "priority": "batch", "tenant": "acme"})
        back = PreprocessedRequest.from_wire(tagged.to_wire())
        assert back.priority == "batch" and back.tenant == "acme"

    def test_class_rank_helpers(self):
        from dynamo_tpu.llm.protocols import class_rank, normalize_priority

        assert class_rank("interactive") > class_rank("standard") \
            > class_rank("batch")
        assert class_rank("weird") == class_rank("standard")
        assert normalize_priority(None) == "standard"
        with pytest.raises(ValueError):
            normalize_priority("urgent")
