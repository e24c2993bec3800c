"""The benchmark's tokenizer: one printable word per id, one id per word.

`Engine.stream` emits a frame only when the decoded text grows, and the
program's `ByteTokenizer` decodes ids >= 256 to nothing — with random weights
almost every sampled id — so its clients never see a first token. This one
satisfies the same `Tokenizer` protocol (engine/tokenizer.py) with
`encode(decode(ids)) == ids` for every id: a prompt of N words is N tokens,
every output token reaches the client as text, and a session's history
re-encodes to exactly the ids the prefix cache holds.
"""

from __future__ import annotations

import zlib
from typing import Sequence

ROLE_IDS = {"<|system|>": 1, "<|user|>": 2, "<|assistant|>": 3}
# what apply_chat_template adds to the words of the messages themselves
TOKENS_PER_MESSAGE = 1  # the role marker
TOKENS_FOR_REPLY = 1  # the trailing <|assistant|>


class WordTokenizer:
    """Id i is the word `t<i>`; text is words separated by single spaces."""

    eos_id = -1  # random weights never stop: every request runs to max_tokens

    def __init__(self, vocab_size: int):
        if vocab_size < 8:
            raise ValueError("WordTokenizer needs vocab_size >= 8")
        self.vocab_size = vocab_size
        self._words = [f"t{i} " for i in range(vocab_size)]

    def encode(self, text: str) -> list[int]:
        out = []
        for w in text.split():
            role = ROLE_IDS.get(w)
            if role is not None:
                out.append(role)
            elif w[0] == "t" and w[1:].isdigit() and int(w[1:]) < self.vocab_size:
                out.append(int(w[1:]))
            else:  # a foreign word: a stable id of its own
                out.append(zlib.crc32(w.encode()) % self.vocab_size)
        return out

    def decode(self, ids: Sequence[int]) -> str:
        words = self._words
        n = self.vocab_size
        return "".join([words[i] for i in ids if 0 <= i < n])

    def apply_chat_template(self, messages: list[dict]) -> str:
        parts = []
        for m in messages:
            content = m.get("content") or ""
            if isinstance(content, list):
                content = " ".join(p.get("text", "") for p in content
                                   if isinstance(p, dict))
            parts.append(f"<|{m.get('role', 'user')}|> {content.strip()} ")
        parts.append("<|assistant|> ")
        return "".join(parts)


def count_words(text: str) -> int:
    return len(text.split())
