"""Model directory from a seed: config.json + a word tokenizer, no weights.

The pattern is `chip_smoke.write_model_dir`'s, copied so that a later
PR's change to the smoke cannot move the benchmark: `LocalModel.prepare`
finds no safetensors and random-initialises from `EngineConfig.seed`;
the tokenizer knows every id of the vocabulary (random weights sample
all of them, and an unknown id would decode to nothing), one
whitespace-free word per id, so a client counts tokens by counting words.
"""

from __future__ import annotations

import json
import os
import random

# reserved ids at the end of the vocabulary, as offsets into its last 256
SPECIAL_OFFSETS = {
    "<|begin_of_text|>": 0,
    "<|start_header_id|>": 6,
    "<|end_header_id|>": 7,
    "<|eot_id|>": 9,
}
CHAT_TEMPLATE = (
    "{{ bos_token }}{% for m in messages %}<|start_header_id|>{{ m['role'] }}"
    "<|end_header_id|>\n\n{{ m['content'] }}<|eot_id|>{% endfor %}"
    "{% if add_generation_prompt %}<|start_header_id|>assistant"
    "<|end_header_id|>\n\n{% endif %}"
)
# bos + 3 header tokens + eot + 3 generation-prompt tokens around one
# user message
TEMPLATE_TOKENS = 8


def make_vocab(vocab_size: int, seed: int) -> list[str]:
    """One distinct whitespace-free word per token id, from the seed."""
    rng = random.Random(seed)
    syl = [c + v for c in "bdfghjklmnprstvwz" for v in "aeiou"]
    words = [
        syl[n // 7225] + syl[n // 85 % 85] + syl[n % 85]
        for n in rng.sample(range(85 ** 3), vocab_size)
    ]
    for i, role in enumerate(("system", "user", "assistant")):
        words[i + 1] = role
    for name, off in SPECIAL_OFFSETS.items():
        words[vocab_size - 256 + off] = name
    return words


def usable_words(words: list[str]) -> list[str]:
    """Words a prompt may use: not a role, not a template token."""
    return [w for w in words[8:] if w not in SPECIAL_OFFSETS]


def write_model_dir(path: str, hf_config: dict, seed: int) -> list[str]:
    """Write config.json, tokenizer.json and tokenizer_config.json under
    `path`; returns the vocabulary (id -> word)."""
    from tokenizers import AddedToken, Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import WhitespaceSplit

    if hf_config["vocab_size"] < 512:
        raise ValueError("the word tokenizer needs a vocabulary of >= 512")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_config, f, indent=1)
    words = make_vocab(hf_config["vocab_size"], seed)
    tok = Tokenizer(WordLevel({w: i for i, w in enumerate(words)},
                              unk_token=words[0]))
    tok.pre_tokenizer = WhitespaceSplit()
    # template tokens are matched before whitespace splitting; not
    # "special", so decode keeps them and every generated id maps back
    tok.add_tokens([
        AddedToken(name, special=False, normalized=False)
        for name in SPECIAL_OFFSETS
    ])
    tok.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({
            "tokenizer_class": "PreTrainedTokenizerFast",
            "bos_token": "<|begin_of_text|>",
            "eos_token": "<|eot_id|>",
            "chat_template": CHAT_TEMPLATE,
        }, f, indent=1)
    return words


def prompt_ids(words: list[str], content: str) -> list[int]:
    """Token ids of one user message under CHAT_TEMPLATE with the
    generation prompt, built from the vocabulary alone (the reference's
    input must not come from the program's preprocessor)."""
    vocab = {w: i for i, w in enumerate(words)}
    bos, sh, eh, eot = (vocab[t] for t in SPECIAL_OFFSETS)
    ids = [bos, sh, vocab["user"], eh]
    ids += [vocab[w] for w in content.split()]
    ids += [eot, sh, vocab["assistant"], eh]
    return ids
