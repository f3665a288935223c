"""A synthetic tokenizer that makes every sampled id visible in the stream.

A preset served with random weights has no tokenizer files, and the server's
fallback ``ByteTokenizer`` decodes only ids < 259: with a ~152k vocabulary
nearly every sampled id decodes to nothing and the server holds the SSE
frame back, so a client sees one frame at the end. This module writes, once
per vocabulary size, a ``tokenizers`` WordLevel tokenizer with one word
``t<id>`` per model id and no EOS; the server loads it with ``--tokenizer``.
Every id then decodes to text, frames leave the server step by step as they
would in a deployment, and lengths are exactly ``max_tokens``.
"""

from __future__ import annotations

import json
import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parent / ".cache"


def tokenizer_dir(vocab_size: int) -> Path:
    return CACHE_DIR / f"tok-{vocab_size}"


def write_tokenizer(vocab_size: int) -> Path:
    """Write the tokenizer for ``vocab_size`` ids if absent; return its dir.

    Plain JSON in the ``tokenizers`` file format (no import of the library
    here: the parent stays light, and the server is what has to load it).
    Written to a temporary name and renamed, so a killed run leaves no
    half-written directory behind."""
    out = tokenizer_dir(vocab_size)
    if (out / "tokenizer.json").exists() and \
            (out / "tokenizer_config.json").exists():
        return out
    tmp = out.with_name(out.name + f".tmp{os.getpid()}")
    tmp.mkdir(parents=True, exist_ok=True)
    vocab = {f"t{i}": i for i in range(vocab_size)}
    tok = {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel", "vocab": vocab, "unk_token": "t0"},
    }
    (tmp / "tokenizer.json").write_text(json.dumps(tok))
    (tmp / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "PreTrainedTokenizerFast",
        "model_max_length": 1 << 30, "clean_up_tokenization_spaces": False}))
    try:
        tmp.rename(out)
    except OSError:
        # Another run won the race; its copy is as good as ours.
        for f in tmp.iterdir():
            f.unlink()
        tmp.rmdir()
    return out
