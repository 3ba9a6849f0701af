"""Serve a token-level engine through a LIVE HttpService.

Drives the real OpenAI surface — admission gate, deadline headers,
tenant stamping, SSE streaming — over a real socket, without needing a
tokenizer dir: prompts go in as token-id lists (the legacy completions
API accepts them) and :class:`TokenCodec` renders output ids as their
decimal text. Real model dirs keep using run.py's full pipeline; this
is the path for tests and multi-process proofs, at any preset.
"""

from __future__ import annotations

import contextlib
from typing import Optional

from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.http.service import HttpService
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
from dynamo_tpu.runtime.pipeline.engine import link


class _NumericDecodeStream:
    def step(self, token_id: int) -> Optional[str]:
        return f"{token_id} "


class TokenCodec:
    """Minimal tokenizer duck-type for the preprocessor/backend pair:
    encodes text as modular byte ids (only exercised by string prompts,
    which its callers never send) and decodes ids to their decimal repr."""

    def __init__(self, vocab_size: int = 256):
        self.vocab = int(vocab_size)

    def encode(self, text: str, add_special_tokens: bool = True) -> list[int]:
        return [1 + (b % (self.vocab - 1)) for b in text.encode()]

    def decode(self, ids, skip_special_tokens: bool = True) -> str:
        return " ".join(str(int(t)) for t in ids)

    def eos_token_ids(self) -> list[int]:
        return []

    def decode_stream(self, skip_special_tokens: bool = True):
        return _NumericDecodeStream()


@contextlib.asynccontextmanager
async def engine_http_service(
    engine,
    model: str = "loadgen",
    vocab_size: int = 256,
    context_length: int = 65536,
    admission=None,
    request_timeout_s: Optional[float] = None,
):
    """Async CM: preprocessor -> backend -> engine pipeline behind a
    started HttpService on 127.0.0.1:<ephemeral>; yields the service
    (``svc.port`` is live)."""
    codec = TokenCodec(vocab_size)
    card = ModelDeploymentCard(
        display_name=model, service_name=model,
        context_length=context_length,
    )
    pipeline = link(
        OpenAIPreprocessor(card, tokenizer=codec), Backend(codec), engine
    )
    svc = HttpService(
        admission=admission, request_timeout_s=request_timeout_s
    )
    svc.manager.add_completion_model(model, pipeline)
    svc.manager.add_chat_model(model, pipeline)
    await svc.start("127.0.0.1", 0)
    try:
        yield svc
    finally:
        await svc.stop()
