"""setup_ckpt_s (s): the step-0 disk save as the trainer's loop waited for
it (snapshot, checksums and write of the blocking save): the ``train.ckpt``
span of the loop's "setup" flight event (ft/recovery.py). The tier's own
"ckpt.persist" event splits it (``snapshot_seconds``,
``checksum_seconds``)."""


def read(run):
    for ev in run.flight:
        if ev["kind"] == "setup" and "train.ckpt" in ev["seconds"]:
            return ev["seconds"]["train.ckpt"]
    return None
