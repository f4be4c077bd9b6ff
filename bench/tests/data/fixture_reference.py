"""A configuration's own ``reference`` module, as a test names it: the
default reference, each call of it recorded in ``CALLS``."""

from bench import reference

CALLS: list[str] = []


class RecordingReference(reference.Reference):
    def check(self, q, answer, guarantee):
        CALLS.append("check")
        return super().check(q, answer, guarantee)


def make_reference(corpus, order, ends, cfg):
    CALLS.append("make_reference")
    return RecordingReference(
        corpus, order, ends, int(cfg["k"]), int(cfg["impact_bits"]),
        float(cfg["bm25_k1"]), float(cfg["bm25_b"]),
    )
