"""Images delivered to the consumer on the device, over the time from the
window's start to the completion of the last batch completed inside it."""


def read(record):
    if record.get("kind") != "loader" or not record.get("elapsed_s"):
        return None
    return record["images"] / record["elapsed_s"]
