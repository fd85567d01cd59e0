# graftlint-fixture: event-conformance expect=0
"""Seeded NEGATIVE fixture: declared-kind emits, non-kind-shaped emit
arguments (free-text signal APIs), and an annotated collision."""

DECLARED_EVENT_KINDS = (
    "fixture.admitted",
    "fixture.preempted",
)


class _Journal:
    def emit(self, kind, **detail):
        return kind


def instrument(journal: _Journal, signals: _Journal):
    journal.emit("fixture.admitted")  # exact reference
    journal.emit("fixture.preempted", generated=7)  # exact reference
    signals.emit("plain text, not a kind")  # not the kind shape: skipped
    signals.emit("topic.changed")  # graftlint: event-ok pubsub topic, not a journal kind
