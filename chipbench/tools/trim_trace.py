"""Trim a profiler trace to what the reduction reads, as a text proto.

    python3 chipbench/tools/trim_trace.py <trace.xplane.pb> <out.textproto> [--max-events N]

Keeps each chip's ``XLA Ops`` line (the first ``N`` events, or all) and
the host's ``chipbench.*`` spans: names and times, which is all the
reduction reads.
``jax.profiler.ProfileData.from_text_proto`` reads the result; the tests
run the reduction on one recorded on the chip.
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path.pop(0)
sys.path[:0] = [ROOT]

from chipbench.yardstick import trace as _trace  # noqa: E402

def _quote(text: str) -> str:
    return '"' + str(text).replace("\\", "\\\\").replace('"', '\\"') + '"'


def trim(data, max_events: int | None) -> str:
    out = []
    for pid, plane in enumerate(data.planes, 1):
        device = _trace.DEVICE_PLANE.match(plane.name)
        names: dict[str, int] = {}
        lines = []
        for lid, line in enumerate(plane.lines, 1):
            if device and line.name != _trace.OPS_LINE:
                continue
            events = [
                e for e in line.events
                if device or e.name.startswith(_trace.SPAN_PREFIX)
            ]
            if max_events and device:
                events = events[:max_events]
            if not events:
                continue
            base = int(min(e.start_ns for e in events))
            body = []
            for e in events:
                mid = names.setdefault(e.name, len(names) + 1)
                off = round((e.start_ns - base) * 1000)
                dur = round((e.end_ns - e.start_ns) * 1000)
                body.append(
                    f"    events {{ metadata_id: {mid} offset_ps: {off} duration_ps: {dur} }}"
                )
            lines.append(
                f"  lines {{\n    id: {lid}\n    name: {_quote(line.name)}\n"
                f"    timestamp_ns: {base}\n" + "\n".join(body) + "\n  }"
            )
        if not lines:
            continue
        meta = [
            f"  event_metadata {{ key: {i} value {{ id: {i} name: {_quote(n)} }} }}"
            for n, i in names.items()
        ]
        out.append(
            f"planes {{\n  id: {pid}\n  name: {_quote(plane.name)}\n"
            + "\n".join(lines + meta) + "\n}"
        )
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("out")
    ap.add_argument("--max-events", type=int, default=None)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    with open(args.out, "w") as fh:
        fh.write(trim(ProfileData.from_file(args.xplane), args.max_events))
    return 0


if __name__ == "__main__":
    sys.exit(main())
