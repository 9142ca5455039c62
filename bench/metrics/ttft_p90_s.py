"""90th percentile of time to first token, from each request's due time,
over every request due in the window."""
from record import percentile


def read(rec):
    return percentile(rec.ttfts(), 90)
