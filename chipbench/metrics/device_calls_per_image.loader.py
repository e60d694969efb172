"""Round trips to the device per image: ``jpeg.transform`` and
``jpeg.color`` spans over ``jpeg.entropy`` spans; none where the program
has neither span."""


def read(record):
    spans = record.get("spans") or {}
    calls = sum(spans[n]["count"] for n in ("jpeg.transform", "jpeg.color")
                if n in spans)
    images = spans.get("jpeg.entropy", {}).get("count")
    if not calls or not images:
        return None
    return calls / images
