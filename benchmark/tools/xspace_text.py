"""Write a profiler trace by hand or cut one down: an XSpace as text
proto, which ``jax.profiler.ProfileData`` turns into the same
``.xplane.pb`` bytes the profiler writes. Used by the hand-made trace of
``tests/test_trace_reduce.py`` and by ``cut_trace.py``."""


def _quote(text):
    return str(text).replace("\\", "\\\\").replace('"', '\\"').replace("\n", " ")


class Plane:
    def __init__(self, plane_id, name):
        self.id, self.name = plane_id, name
        self.lines, self.event_ids, self.stat_ids = [], {}, {}

    def line(self, name, events):
        """events: (name, start_ns, end_ns, {stat: str})"""
        out = []
        for ev_name, start, end, stats in events:
            eid = self.event_ids.setdefault(ev_name, len(self.event_ids) + 1)
            stat_text = "".join(
                ' stats { metadata_id: %d str_value: "%s" }'
                % (self.stat_ids.setdefault(k, len(self.stat_ids) + 1), _quote(v))
                for k, v in stats.items()
            )
            out.append(
                "events { metadata_id: %d offset_ps: %d duration_ps: %d%s }"
                % (eid, round(start * 1000), round((end - start) * 1000), stat_text)
            )
        self.lines.append(
            'lines { id: %d name: "%s" timestamp_ns: 0 %s }'
            % (len(self.lines) + 1, _quote(name), "\n".join(out))
        )

    def text(self):
        meta = "\n".join(
            'event_metadata { key: %d value { id: %d name: "%s" } }'
            % (i, i, _quote(n))
            for n, i in self.event_ids.items()
        ) + "\n".join(
            'stat_metadata { key: %d value { id: %d name: "%s" } }'
            % (i, i, _quote(n))
            for n, i in self.stat_ids.items()
        )
        return 'planes { id: %d name: "%s"\n%s\n%s }' % (
            self.id, _quote(self.name), "\n".join(self.lines), meta
        )  # fmt: skip


def to_xplane_bytes(planes):
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(
        "\n".join(p.text() for p in planes)
    )
