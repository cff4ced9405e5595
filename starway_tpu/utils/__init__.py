"""Utilities: checkpointing, data batching, chip helpers."""

from .data import TokenBatcher, load_tokens

__all__ = ["TokenBatcher", "load_tokens"]
