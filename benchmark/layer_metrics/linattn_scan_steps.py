"""``linattn_scan_steps``: dependent steps of the scan that carries the
gated delta rule's state along a sequence — sequence / chunk, 128 at
8,192 rows in chunks of 64 — as the program's scope table found it in
the compiled block program (the gauge ``linear_attention.scan_steps``
labelled ``program=``: the trip count of the ``while`` under the scope
``gated_delta``; 0 where the program holds no such loop: a kernel that
carries the state itself).  Nothing where the program sets no such
gauge."""

from benchmark.layer_metrics import scoped


def read(record, name):
    scopes = scoped._program("programs", "scopes")
    registry = scoped._program("metrics", "registry")
    if scopes is None or registry is None or \
            scopes(scoped.PROGRAM) is None:
        return None
    found = registry.peek("linear_attention.scan_steps",
                          {"program": scoped.PROGRAM})
    return None if found is None else found.value
