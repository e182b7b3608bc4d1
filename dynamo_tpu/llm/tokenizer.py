"""Tokenizer abstraction: HF `tokenizers` (local files) + byte-level fallback.

The reference embeds HF tokenizers behind its preprocessor (ref: lib/llm/src/
preprocessor.rs uses the `tokenizers` crate; tokenizer config travels in the
ModelDeploymentCard). We support:

  * HfTokenizer  — loads tokenizer.json via the `tokenizers` library
  * ByteTokenizer — 256 byte vocab + special tokens; zero-asset, used for
    tests and the mocker (this environment has no model downloads)

Incremental (streaming) detokenization uses the prefix-offset technique: keep
decoding the tail window of tokens and only emit the stable UTF-8 suffix, so
multi-token unicode and SentencePiece prefix spaces render correctly.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence


class Tokenizer:
    eos_token_ids: list[int] = []
    vocab_size: int = 0
    chat_template: Optional[str] = None
    # How many trailing tokens may still merge with future tokens when
    # decoding incrementally (BPE merges / sentencepiece boundary spaces).
    # Byte-level decode is prefix-stable, so 0 there.
    stable_window: int = 4

    def encode(self, text: str) -> list[int]:
        raise NotImplementedError

    def decode(self, token_ids: Sequence[int]) -> str:
        raise NotImplementedError

    def token_text(self, token_id: int) -> Optional[str]:
        """Raw vocab string of one token (e.g. 'Ġhello', 'â' for a lone
        UTF-8 continuation byte under byte-level BPE), or None if
        unknown. Unlike decode(), never lossy: guided decoding inverts
        byte-level-BPE strings back to true bytes (llm/guided.py)."""
        return None

    def spec(self) -> dict:
        """Serializable description for the ModelDeploymentCard."""
        raise NotImplementedError


class ByteTokenizer(Tokenizer):
    """Byte-level: token i (< 256) is byte i. Special tokens above 255.
    Mirrors the role of the mocker's tokenizer-free operation."""

    BOS = 256
    EOS = 257
    PAD = 258
    IM_START = 259
    IM_END = 260

    SPECIALS = {BOS: "<s>", EOS: "</s>", PAD: "<pad>",
                IM_START: "<|im_start|>", IM_END: "<|im_end|>"}

    def __init__(self) -> None:
        self.eos_token_ids = [self.EOS, self.IM_END]
        self.vocab_size = 512  # headroom above 261 for model round numbers
        self.stable_window = 0

    def encode(self, text: str) -> list[int]:
        return list(text.encode("utf-8", errors="replace"))

    def decode(self, token_ids: Sequence[int]) -> str:
        out: list[str] = []
        buf = bytearray()
        for tok in token_ids:
            if tok < 256:
                buf.append(tok)
            else:
                if buf:
                    out.append(buf.decode("utf-8", errors="replace"))
                    buf.clear()
                out.append(self.SPECIALS.get(tok, f"<unk:{tok}>"))
        if buf:
            out.append(buf.decode("utf-8", errors="replace"))
        return "".join(out)

    def spec(self) -> dict:
        return {"kind": "byte"}


class HfTokenizer(Tokenizer):
    def __init__(self, path: str) -> None:
        from tokenizers import Tokenizer as _HfTok

        tok_file = path
        if os.path.isdir(path):
            tok_file = os.path.join(path, "tokenizer.json")
        self._tok = _HfTok.from_file(tok_file)
        self._path = path
        self.vocab_size = self._tok.get_vocab_size()
        self.eos_token_ids = []
        self.chat_template = None
        # Pull eos/chat_template from sibling config files if present.
        cfg_dir = path if os.path.isdir(path) else os.path.dirname(path)
        self._load_config(cfg_dir)

    def _load_config(self, cfg_dir: str) -> None:
        import json

        tcfg_path = os.path.join(cfg_dir, "tokenizer_config.json")
        gcfg_path = os.path.join(cfg_dir, "generation_config.json")
        if os.path.exists(tcfg_path):
            try:
                with open(tcfg_path) as f:
                    tcfg = json.load(f)
                self.chat_template = tcfg.get("chat_template")
                eos = tcfg.get("eos_token")
                if isinstance(eos, dict):
                    eos = eos.get("content")
                if isinstance(eos, str):
                    tid = self._tok.token_to_id(eos)
                    if tid is not None:
                        self.eos_token_ids.append(tid)
            except (OSError, ValueError):
                pass
        if os.path.exists(gcfg_path):
            try:
                with open(gcfg_path) as f:
                    gcfg = json.load(f)
                eos = gcfg.get("eos_token_id")
                if isinstance(eos, int):
                    eos = [eos]
                if isinstance(eos, list):
                    self.eos_token_ids.extend(
                        e for e in eos if e not in self.eos_token_ids
                    )
            except (OSError, ValueError):
                pass

    def encode(self, text: str) -> list[int]:
        return self._tok.encode(text, add_special_tokens=False).ids

    def decode(self, token_ids: Sequence[int]) -> str:
        return self._tok.decode(list(token_ids), skip_special_tokens=True)

    def token_text(self, token_id: int) -> Optional[str]:
        return self._tok.id_to_token(token_id)

    def spec(self) -> dict:
        return {"kind": "hf", "path": self._path}


def load_tokenizer(spec: dict) -> Tokenizer:
    kind = spec.get("kind", "byte")
    if kind == "byte":
        return ByteTokenizer()
    if kind == "hf":
        return HfTokenizer(spec["path"])
    raise ValueError(f"unknown tokenizer spec: {spec!r}")


class IncrementalDetokenizer:
    """Streaming decode: emits only text that can no longer change as more
    tokens arrive (ref: Backend detokenizer hot loop, lib/llm/src/backend.rs).

    A push decodes a bounded tail, never a span that grows with the
    answer: the tokens since the anchor `_ctx_start`, diffed against the
    length already emitted from there (the reference's prefix-offset /
    read-offset pair). The anchor follows the emissions. Behind a
    prefix-stable tokenizer (`stable_window == 0`: byte-level) it moves
    onto every clean end, so a push decodes the pushed ids and whatever
    partial UTF-8 bytes are held back; otherwise it is re-set
    `_CTX_KEEP` tokens behind the stable edge once the span passes
    `_CTX_KEEP + stable_window`, and a decode is handed at most that
    many ids plus the pushed ones."""

    # Keep this many already-stable tokens as decode context when sliding the
    # anchor (BPE/sentencepiece boundary effects cancel within the context).
    _CTX_KEEP = 16

    def __init__(self, tokenizer: Tokenizer, window: Optional[int] = None) -> None:
        self._tok = tokenizer
        self._ids: list[int] = []
        self._window = tokenizer.stable_window if window is None else window
        # decode(a + b) == decode(a) + decode(b) wherever decode(a) ends
        # clean: the tokenizer's own word for it is stable_window == 0
        self._prefix_stable = tokenizer.stable_window == 0
        self._ctx_start = 0  # decode-anchor token index
        self._stable_tokens = 0  # tokens whose text has been emitted
        self._prev_len = 0  # len(decode(ids[_ctx_start:_stable_tokens])) - held-back "�"
        self.decoded_tokens = 0  # ids handed to Tokenizer.decode so far

    @property
    def pushed_tokens(self) -> int:
        return len(self._ids)

    def _decode(self, start: int, stop: int) -> str:
        self.decoded_tokens += stop - start
        return self._tok.decode(self._ids[start:stop])

    def push(self, token_ids: Sequence[int]) -> str:
        """Add tokens, return newly-stable text (may be '')."""
        self._ids.extend(token_ids)
        n = len(self._ids)
        stable = n if self._window == 0 else max(0, n - self._window)
        if stable <= self._stable_tokens:
            return ""
        text = self._decode(self._ctx_start, stable)
        # Never emit a trailing replacement char (partial UTF-8 sequence);
        # it re-decodes complete once the rest of the char arrives.
        candidate = text[self._prev_len :].rstrip("�")
        self._stable_tokens = stable
        self._prev_len += len(candidate)
        if self._prefix_stable and self._prev_len == len(text):
            # nothing held back: what follows decodes on its own
            self._ctx_start = stable
            self._prev_len = 0
        elif stable - self._ctx_start > self._CTX_KEEP + self._window:
            # as many chars stay held back behind the new anchor as were
            # (counted, not stripped: a byte-fallback decoder turns a whole
            # run of bytes to "�" while its last char is partial, chars
            # already emitted among them)
            held = max(0, len(text) - self._prev_len)
            self._ctx_start = stable - self._CTX_KEEP
            self._prev_len = max(
                0, len(self._decode(self._ctx_start, stable)) - held)
        return candidate

    def flush(self) -> str:
        """Emit everything outstanding (end of stream)."""
        full = self._decode(self._ctx_start, len(self._ids))
        out = full[self._prev_len :]
        self._prev_len = len(full)
        self._stable_tokens = len(self._ids)
        return out
