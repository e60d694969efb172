"""The host half of the augmentation (``augment.collate`` spans: the crop
draws and the copy into the padded batch) per image collated."""


def read(record):
    spans = record.get("spans") or {}
    span = spans.get("augment.collate")
    if not span or not span["count"] or not record.get("batch_size"):
        return None
    return 1e3 * span["total_s"] / (span["count"] * record["batch_size"])
