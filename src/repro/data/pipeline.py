"""Data pipelines: deterministic synthetic streams + memmap token files.

Production posture:

- **Determinism / checkpointability**: every iterator exposes ``state()`` /
  ``load_state()`` (a tiny dict) that the checkpointer persists — resuming a
  run replays the exact batch sequence (bit-identical loss curves, verified
  in tests).
- **DP sharding**: each data-parallel rank reads only its slice
  (``shard_id`` / ``num_shards``); on a single host this is a no-op but the
  slicing logic is exercised by tests.
- **Straggler hiding**: a background prefetch thread keeps a small queue of
  ready batches, so host-side input processing never stalls the device step
  (the first line of straggler mitigation in synchronous SPMD training).
"""
from __future__ import annotations

import queue
import threading
from typing import Iterator, Optional

import numpy as np

from repro.configs.base import ModelConfig
from repro.obs.trace import program_span


class SyntheticTokens:
    """Deterministic synthetic batches matching an arch's input structure.

    Uses a counter-keyed PRNG (numpy Philox) so ``state()`` is just the step
    counter — restore is O(1), no stream replay needed.
    """

    def __init__(self, cfg: ModelConfig, batch: int, seq: int, seed: int = 0,
                 shard_id: int = 0, num_shards: int = 1):
        if batch % num_shards:
            raise ValueError("batch must divide across data shards")
        self.cfg, self.batch, self.seq = cfg, batch, seq
        self.seed, self.shard_id, self.num_shards = seed, shard_id, num_shards
        self._step = 0

    # -- checkpointable iterator protocol -----------------------------------
    def state(self) -> dict:
        return {"step": self._step, "seed": self.seed}

    def load_state(self, state: dict) -> None:
        self._step = int(state["step"])
        self.seed = int(state["seed"])

    # -- iteration -----------------------------------------------------------
    def _rng(self, step: int) -> np.random.Generator:
        return np.random.Generator(np.random.Philox(
            key=self.seed, counter=[step, self.shard_id, 0, 0]))

    def next_batch(self) -> dict:
        rng = self._rng(self._step)
        self._step += 1
        b = self.batch // self.num_shards
        cfg = self.cfg
        if cfg.frontend == "frames":
            dim = cfg.frontend_dim or cfg.d_model
            return {
                "features": rng.standard_normal(
                    (b, self.seq, dim), dtype=np.float32),
                "targets": rng.integers(
                    0, cfg.vocab_size, (b, self.seq), dtype=np.int32),
            }
        out = {
            "inputs": rng.integers(0, cfg.vocab_size, (b, self.seq),
                                   dtype=np.int32),
            "targets": rng.integers(0, cfg.vocab_size, (b, self.seq),
                                    dtype=np.int32),
        }
        if cfg.frontend == "patches":
            dim = cfg.frontend_dim or cfg.d_model
            n_p = max(4, min(64, self.seq // 4))
            out["patches"] = rng.standard_normal(
                (b, n_p, dim), dtype=np.float32)
        return out

    def __iter__(self) -> Iterator[dict]:
        while True:
            yield self.next_batch()


class MemmapTokens:
    """Packed next-token-prediction batches from a flat binary token file.

    File format: raw little-endian int32 tokens (``make_token_file`` builds
    one).  Sequences are drawn as contiguous windows; window ``w`` of rank
    ``r`` at step ``t`` is a pure function of (seed, t, r) — checkpointable
    like the synthetic stream.
    """

    def __init__(self, path: str, batch: int, seq: int, seed: int = 0,
                 shard_id: int = 0, num_shards: int = 1):
        self.tokens = np.memmap(path, dtype=np.int32, mode="r")
        if len(self.tokens) < seq + 2:
            raise ValueError("token file too small for seq length")
        self.batch, self.seq = batch, seq
        self.seed, self.shard_id, self.num_shards = seed, shard_id, num_shards
        self._step = 0

    def state(self) -> dict:
        return {"step": self._step, "seed": self.seed}

    def load_state(self, state: dict) -> None:
        self._step = int(state["step"])
        self.seed = int(state["seed"])

    def next_batch(self) -> dict:
        rng = np.random.Generator(np.random.Philox(
            key=self.seed, counter=[self._step, self.shard_id, 0, 0]))
        self._step += 1
        b = self.batch // self.num_shards
        starts = rng.integers(0, len(self.tokens) - self.seq - 1, size=b)
        rows = np.stack([self.tokens[s:s + self.seq + 1] for s in starts])
        return {"inputs": rows[:, :-1].astype(np.int32),
                "targets": rows[:, 1:].astype(np.int32)}

    def __iter__(self) -> Iterator[dict]:
        while True:
            yield self.next_batch()


def make_token_file(path: str, n_tokens: int, vocab: int, seed: int = 0):
    rng = np.random.Generator(np.random.Philox(key=seed))
    arr = rng.integers(0, vocab, size=n_tokens, dtype=np.int32)
    arr.tofile(path)
    return path


class Prefetcher:
    """Background-thread prefetch queue over any checkpointable iterator.

    ``state()`` reflects the number of batches *consumed*, not produced, so
    a checkpoint/restore never skips or replays batches that were sitting
    in the queue.  ``next_batch`` opens a ``data wait`` program span
    (:func:`repro.obs.trace.program_span`) around its blocking get, so a
    profiler trace shows how long the step waited for input.
    """

    def __init__(self, source, depth: int = 2):
        self.source = source
        self._consumed = 0
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def _fill(self):
        while not self._stop.is_set():
            batch = self.source.next_batch()
            while not self._stop.is_set():
                try:
                    self._queue.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def next_batch(self) -> dict:
        with program_span("data wait"):
            batch = self._queue.get()
        self._consumed += 1
        return batch

    def state(self) -> dict:
        st = self.source.state()
        st["step"] = self._consumed  # ignore produced-but-unconsumed
        return st

    def load_state(self, state: dict) -> None:
        self.source.load_state(state)
        self._consumed = int(state["step"])

    def close(self):
        self._stop.set()
        self._thread.join(timeout=1.0)
