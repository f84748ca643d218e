"""What `jax.profiler.ProfileData` does not give: the statistics kept
with an event's METADATA in an `.xplane.pb`. A TPU's operation events
carry only their times; the HLO `op_name` of an operation, which holds
its `jax.named_scope` path, is a string statistic of the event's
metadata entry. This reads just those from the protobuf's wire format
(`XSpace.planes[].event_metadata[].stats[].str_value`, field numbers of
tsl/profiler/protobuf/xplane.proto), skipping the lines and events.
"""

from __future__ import annotations


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one message; a value is an
    int (varint, fixed) or a memoryview slice (length-delimited)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        yield num, wt, val


def event_metadata_text(path: str) -> dict[str, dict[str, str]]:
    """For every plane, by name: each event metadata entry's name mapped
    to its string statistics joined by spaces."""
    with open(path, "rb") as fp:
        space = memoryview(fp.read())
    out: dict[str, dict[str, str]] = {}
    for num, wt, plane in _fields(space):
        if num != 1 or wt != 2:
            continue
        name, entries = "", []
        for pnum, pwt, val in _fields(plane):
            if pnum == 2 and pwt == 2:
                name = bytes(val).decode(errors="replace")
            elif pnum == 4 and pwt == 2:
                entries.append(val)
        texts: dict[str, str] = {}
        for entry in entries:  # map<int64, XEventMetadata>
            for enum_, ewt, meta in _fields(entry):
                if enum_ != 2 or ewt != 2:
                    continue
                ev_name, strings = "", []
                for mnum, mwt, mval in _fields(meta):
                    if mnum == 2 and mwt == 2:
                        ev_name = bytes(mval).decode(errors="replace")
                    elif mnum == 5 and mwt == 2:  # XStat
                        for snum, swt, sval in _fields(mval):
                            if snum == 5 and swt == 2:  # str_value
                                strings.append(
                                    bytes(sval).decode(errors="replace"))
                if strings:
                    texts[ev_name] = " ".join(strings)
        out[name] = texts
    return out
