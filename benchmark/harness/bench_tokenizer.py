"""The benchmark's tokenizer: one whitespace-separated word per token.

No tokenizer.json can be fetched (no network), and the program's hermetic
ByteTokenizer knows 259 ids of Mistral's 32768: nearly every generated
token would decode to the empty string and the OpenAI surface writes no
SSE frame for empty text, so a client could not see a token arrive. This
one gives every id a visible word ("w<id>"), so that one token is one
frame and a prompt of n words is n tokens, and it has no end-of-sequence
id: a request generates exactly the tokens it asked for.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, Sequence


class WordTokenizer:
    def __init__(self, vocab_size: int):
        self.vocab_size = int(vocab_size)
        self.pad_id = 0
        self.bos_id = 1
        self.eos_id = None
        self.eos_ids: set = set()
        # BERT-style specials for the encoders (serving/encoders.py).
        self.cls_id = 2
        self.sep_id = 3

    def _id(self, word: str) -> int:
        if word[0] == "w" and word[1:].isdigit():
            return int(word[1:]) % self.vocab_size
        return zlib.crc32(word.encode()) % self.vocab_size

    def encode(self, text: str, add_bos: bool = False) -> List[int]:
        ids = [self._id(w) for w in text.split()]
        return ([self.bos_id] if add_bos else []) + ids

    def decode(self, ids: Sequence[int]) -> str:
        return "".join(f" w{int(i)}" for i in ids)

    def apply_chat_template(self, messages: Sequence[Dict[str, str]],
                            add_generation_prompt: bool = True) -> str:
        parts = [f"<|{m['role']}|> {m['content']} " for m in messages]
        if add_generation_prompt:
            parts.append("<|assistant|> ")
        return "".join(parts)
