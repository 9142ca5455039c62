"""99th percentile of the gaps between consecutive streamed tokens of a
request, pooled over all requests, both tokens inside the window."""
from record import percentile


def read(rec):
    return percentile(rec.tbt_gaps(), 99)
