"""The system under test as the harness sees it: ``LMEngine`` behind a few
calls. The window drives ``submit`` and ``step_iteration`` and nothing else.

This file is the one place the benchmark reaches into the engine, for two
things it has no public way to see (both listed in PERF.md for the
``tracing`` issue). Tokens while a request runs: ``LMEngine.results`` holds
finished requests only, so ``submit`` keeps the engine's own ``_Request``
and ``progress`` reads the length of its ``out``. And the keys and values
the timed programs wrote: ``step`` notes which request each free slot is
about to take (``_admit`` fills free slots in ascending order from the head
of the queue), and once the window has closed ``resident`` copies the rows
of the last request of a slot out of the contiguous stores.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from nnstreamer_tpu.serving.lm_engine import LMEngine, next_pow2_bucket


class LMEngineAdapter:
    def __init__(self, engine: LMEngine) -> None:
        self.engine = engine
        self._last: Dict[int, Any] = {}   # slot -> the request last in it
        self._rule_held = True

    def submit(self, prompt: np.ndarray, max_new: int) -> Any:
        rid = self.engine.submit(prompt, max_new)
        req = self.engine._queue[-1]
        if req.rid != rid:
            raise RuntimeError("submitted request is not last in the queue")
        return req

    def step(self) -> bool:
        eng = self.engine
        free = [s for s, r in enumerate(eng._slot_req) if r is None]
        for slot, req in zip(free, list(eng._queue)):
            self._last[slot] = req
        more = eng.step_iteration()
        for slot, req in enumerate(eng._slot_req):
            if req is not None and self._last.get(slot) is not req:
                self._rule_held = False   # admission no longer works so
        return more

    def pending(self) -> int:
        return self.engine.pending()

    @staticmethod
    def progress(handle: Any) -> int:
        return len(handle.out)

    @staticmethod
    def tokens(handle: Any) -> List[int]:
        return list(handle.out)

    def resident(self, most: int) -> List[Tuple[Any, np.ndarray, np.ndarray]]:
        """(request, keys, values) for up to ``most`` slots whose last
        request has finished, longest first: the rows the prefill and the
        decode steps wrote for its prompt and all but its last token,
        (layers*heads, rows, head) float32 on the host. Later steps of an
        empty slot write past those rows only."""
        eng = self.engine
        if not self._rule_held or eng._kc is None:
            return []
        done = [(slot, req) for slot, req in self._last.items()
                if req.done and eng._slot_req[slot] is None]
        done.sort(key=lambda sr: -(sr[1].prompt.size + len(sr[1].out)))
        out = []
        for slot, req in done[:most]:
            rows = int(req.prompt.size) + len(req.out) - 1
            out.append((req, np.asarray(eng._kc[slot, :, :rows]),
                        np.asarray(eng._vc[slot, :, :rows])))
        return out

    def stats(self) -> Dict[str, float]:
        return dict(self.engine.stats)

    def bucket_of(self, prompt_len: int) -> int:
        """The padded prefill length a prompt compiles to: the engine's
        default rule (next power of two, capped at ``max_len``)."""
        return min(next_pow2_bucket(int(prompt_len)), self.engine.max_len)

    def buckets(self, prompt_lens: Sequence[int]) -> List[int]:
        return sorted({self.bucket_of(t) for t in prompt_lens})

    def warm(self, prompt_lens: Sequence[int], vocab: int) -> None:
        """One warm call of every program the traffic uses: a prefill of
        each bucket, and decode chunks of ``chunk`` steps and each power
        of two below it (a lone request with 2*chunk tokens to make runs
        chunk, chunk/2, .., 1 steps)."""
        first = True
        for b in self.buckets(prompt_lens):
            # the longest prompt of the bucket that leaves room to decode
            t = min(b, self.engine.max_len - 2 * self.engine.chunk)
            ids = (np.arange(t, dtype=np.int64) * 7919 % vocab
                   ).astype(np.int32)
            self.engine.submit(ids, 2 * self.engine.chunk if first else 2)
            first = False
            while self.engine.step_iteration():
                pass

    def free(self) -> None:
        """Drop the engine's device state (weights and KV stores) so the
        reference fits beside nothing."""
        eng = self.engine
        self.engine = None
        for name in ("params", "_kc", "_vc", "_tokens", "_pos", "_skeys",
                     "_temp", "_topk", "_topp"):
            setattr(eng, name, None)
