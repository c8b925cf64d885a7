"""Paged-attention kernel's share of its roofline; moves itl_p95_ms."""
from bench.readers import paged_attention_roofline as read  # noqa: F401
