"""``setup_s``: process start to the start of the measured window — import,
weights made on the device, compile or cache load of the cell's shapes, the
first checked steps or the lead-in traffic.  Host clock."""


def read(rec: dict):
    return rec["setup_s"]
