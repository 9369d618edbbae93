"""Build the directory `python -m dynamo_tpu.run` serves from.

A configuration is `configs/<name>/config.json` (the published file with
only `num_hidden_layers` changed). The launcher's `build_card` takes a
directory; with no `*.safetensors` in it the engine random-initialises on
the device. This module copies `config.json` into the run's output
directory and writes a SYNTHETIC tokenizer beside it: a word-level
vocabulary `w0 .. w<vocab-1>`, one word per id. The published tokenizers
are not in this repo (no network), and the byte tokenizer a bare directory
gets decodes ids >= 259 to nothing, so a random-weight model would stream
almost no text frames and a client could not time tokens. With one
printable word per id every committed token is one SSE frame, and a prompt
of n words is exactly n tokens plus the template's.

No JAX import here: the load generator's tests use it too.
"""
from __future__ import annotations

import json
import os
import shutil

# renders to "w3 <content> w4": two template tokens around the user turn
CHAT_TEMPLATE = ("{% for message in messages %}w3 {{ message.content }} w4"
                 "{% endfor %}")


def write_tokenizer(path: str, vocab_size: int) -> None:
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import WhitespaceSplit
    vocab = {f"w{i}": i for i in range(vocab_size)}
    tok = Tokenizer(WordLevel(vocab, unk_token="w0"))
    tok.pre_tokenizer = WhitespaceSplit()
    tok.save(path)


def build_model_dir(config_dir: str, out_dir: str) -> str:
    """`out_dir/<config name>/` with config.json, tokenizer.json and
    tokenizer_config.json; returns it. The directory's base name is the
    model name the frontend serves."""
    with open(os.path.join(config_dir, "config.json")) as f:
        cfg = json.load(f)
    dst = os.path.join(out_dir, os.path.basename(config_dir.rstrip("/")))
    os.makedirs(dst, exist_ok=True)
    shutil.copyfile(os.path.join(config_dir, "config.json"),
                    os.path.join(dst, "config.json"))
    write_tokenizer(os.path.join(dst, "tokenizer.json"),
                    int(cfg["vocab_size"]))
    with open(os.path.join(dst, "tokenizer_config.json"), "w") as f:
        json.dump({"chat_template": CHAT_TEMPLATE}, f)
    return dst
