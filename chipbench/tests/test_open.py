"""The open loop's latency of requests the server never answered."""
import math

from chipbench import common
from chipbench.drivers import open as open_loop


class _Server:
    """Takes every other request and serves one request per step."""

    def __init__(self):
        self.queue, self.batches, self.n = [], [], 0

    @property
    def queue_depth(self):
        return len(self.queue)

    def submit(self, req):
        self.n += 1
        if self.n % 2 == 0:
            return False
        self.queue.append(req)
        return True

    def step(self):
        req = self.queue.pop(0)

        class Done:
            rid, pred = req.rid, None

        class Batch:
            rids = (req.rid,)

        self.batches.append(Batch)
        return [Done]


class _Serving:
    def __init__(self):
        self.server, self.next_rid = _Server(), 0

    def make(self, due):
        class Req:
            rid = self.next_rid

        self.next_rid += 1
        return Req


class _Ctx:
    spans = common.Spans(False)


def test_rejected_requests_are_late_until_the_end():
    due = [0.0, 0.01, 0.02, 0.03]
    rec = open_loop.new_record()
    end = open_loop.loop(_Serving(), _Ctx(), due, rec)
    assert sorted(rec["unserved"]) == [1, 3]
    for rid, d in rec["unserved"].items():
        assert rec["latency"][rid] == end - d
    assert all(math.isfinite(v) for v in rec["latency"].values())
    assert sorted(rec["served"]) == [0, 2]
