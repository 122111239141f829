"""1 - (union of device-op intervals / traced window), mean over the
chips' planes, from the xplane; the arithmetic is xplane.reduce's. Read
for train_device_idle_share, serve_device_idle_share and
data_device_idle_share alike (harness.reader): what the idle share moves
end to end differs with the kind of cell, and a metric has one `moves`."""

from ..xplane import idle_share_percent


def read(record):
    return idle_share_percent(record.get("trace"))
