"""consumer.devpool_upload_share: share of the consumer's device-block
reads that had to upload a host block (``devpool_uploads`` over
``devpool_hits + devpool_uploads``), over the window's passes."""


def read(run):
    up = sum(s["devpool_uploads"] for s in run.pass_stats)
    total = up + sum(s["devpool_hits"] for s in run.pass_stats)
    if total == 0:
        return None
    return 100.0 * up / total
