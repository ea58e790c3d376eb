"""Declared registry of hello-advertised capability flags.

The driver/daemon wire protocol is version-negotiated per connection:
the daemon's ``handle_hello_driver`` reply advertises what it can do,
the driver stores the bits on its :class:`DaemonHandle` and consults
them before using any capability-gated frame shape. PR-10/11 reviews
caught the same drift by hand four times — a flag advertised but never
checked, or a gated frame sent without checking the peer — so the
shape of the negotiation now lives HERE, as data, and raylint's
``capability-drift`` pass machine-checks all three legs:

- every ``kind: "hello"`` flag is advertised (a key in some
  ``handle_hello*`` reply dict) and its ``guard`` attribute is read
  somewhere (a dead flag is protocol cruft);
- every ``kind: "frame"`` flag is written at some wire send site and
  read (``msg.get(...)``/``msg[...]``) at some receive site;
- every send site of a ``frame`` flag with a non-empty ``requires``
  list is dominated by a check of one of those hello guards — in the
  sending function itself, in a direct caller, or in a helper the
  caller consults (``execute_task`` -> ``_submit_coalescer`` reads
  ``_batch_supported`` before ``_submit_batched`` fires).

Adding a capability: add the hello-reply key + its DaemonHandle guard
attribute here FIRST, then wire the advertiser and the gates — raylint
fails until all legs exist. This dict is parsed statically (it must
stay a pure literal) and imported nowhere hot.
"""

CAPABILITY_FLAGS = {
    # daemon -> driver hello-reply capability bits; "guard" names the
    # DaemonHandle attribute the driver must consult before using the
    # capability on the wire.
    "batch": {
        "kind": "hello",
        "guard": "_batch_supported",
        "doc": "daemon accepts push_task_batch coalesced submissions",
    },
    "result_batch": {
        "kind": "hello",
        "guard": "_result_batch",
        "doc": "daemon batches completions via the reply pump",
    },
    "objectplane": {
        "kind": "hello",
        "guard": "objectplane",
        "doc": "daemon exposes the shm object arena (zero-copy gets)",
    },
    "tenancy": {
        "kind": "hello",
        "guard": "_tenancy_supported",
        "doc": "daemon accepts tenancy_sync job-table frames "
               "(per-job quota/weight federation); drivers that never "
               "see the bit fall back to unconditional admission",
    },
    # driver -> daemon per-frame flags on capability-gated frames;
    # "requires" lists the hello guards that must dominate the send.
    "via_pump": {
        "kind": "frame",
        "requires": ["_result_batch"],
        "doc": "submit_task completion may ride the reply pump",
    },
    "term_pump": {
        "kind": "frame",
        "requires": ["_result_batch", "_batch_supported"],
        "doc": "terminations for this task may ride the reply pump",
    },
    "slot_ok": {
        "kind": "frame",
        "requires": [],
        "doc": "this driver understands ext-slot object grants "
               "(self-describing: reflects the sender's own ability)",
    },
    "fence": {
        "kind": "hello",
        "guard": "_fence_supported",
        "doc": "daemon stamps its registration epoch (ep) and the task "
               "attempt (att) into result/termination frames so the "
               "driver can fence stale deliveries across healed "
               "partitions",
    },
    "ep": {
        "kind": "frame",
        "requires": [],
        "doc": "daemon registration epoch stamped on a result frame "
               "(self-describing: an unstamped frame is simply never "
               "fenced, so no hello guard dominates the send)",
    },
    "att": {
        "kind": "frame",
        "requires": [],
        "doc": "task attempt number stamped on a result frame "
               "(self-describing, like ep)",
    },
}
