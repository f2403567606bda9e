"""Closed-loop serving: ``clients`` callers, each sending its next request
of ``rows`` rows as soon as its last one returned.  The end-to-end number
is the rows of the requests completed inside the window over its
length."""
from __future__ import annotations

import threading

import numpy as np

from bench import traffic
from bench.harness import clock, log
from bench.loads import Serve, pow2


class ServeClosed(Serve):
    def buckets(self):
        return [min(pow2(int(self.mix["rows"])),
                    pow2(int(self.mix["max_batch"])))]

    def window(self, seconds: float):
        clients = int(self.mix["clients"])
        size = int(self.mix["rows"])
        cap = int(self.mix["max_requests"])
        self.offset = traffic.closed_offsets(self.mix, self.seed, cap,
                                             self.pool.shape[0])
        self.rows = np.full(cap, size)
        # answers kept for the check: a seeded share of the request
        # indices, so that some hundreds of a window's requests are kept
        keep = set(self._sample(int(self.mix["sample_of"])))
        self.results = {}
        done = np.full(cap, np.inf)
        nxt = iter(range(cap))
        lock = threading.Lock()
        errors = []
        self.snap0 = self.fe.snapshot()
        t_open = clock()
        t_close = t_open + seconds

        def client():
            while clock() < t_close:
                with lock:
                    k = next(nxt, None)
                if k is None:
                    errors.append("request cap reached")
                    return
                try:
                    z = self.fe.submit(self._request(k)).result(timeout=120)
                except Exception as e:  # noqa: BLE001 - counted as failed
                    errors.append(repr(e))
                    return
                done[k] = clock()
                if k in keep:
                    self.results[k] = z

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_end = clock()
        self.counts = self.serve_counts()
        sent = self.counts["requests"]
        self.attempted = sent
        self.failed = int(np.sum(np.isinf(done[:sent])))
        self.results = {k: z for k, z in self.results.items() if k < sent}
        in_window = int(np.sum(done[:sent] <= t_close))
        self.rows_per_s = in_window * size / seconds
        log(f"closed loop: {clients} clients x {size} rows for {seconds} s, "
            f"requests sent={sent} succeeded={sent - self.failed} "
            f"failed={self.failed} completed in window={in_window}, "
            f"rows/s={self.rows_per_s:.3f}, batches={self.counts['batches']}"
            + (f", errors {errors[:3]}" if errors else ""))
        if errors and errors[0] == "request cap reached":
            raise RuntimeError("closed loop ran out of pre-drawn requests: "
                               "raise max_requests in the mix")
        return t_open, t_end

    def e2e(self) -> dict:
        return {"serve_rows_per_s": self.rows_per_s}


LOAD = ServeClosed
