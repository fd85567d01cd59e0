"""The server entry's side thread: the parent polls for `<trace-dir>/done`
and reads it the moment it exists, so the file has to appear whole. (Written
in place, it was seen empty between `open` and `close` whenever the side
thread waited for the interpreter lock there: the driver's first traced run
of PR 24 ended so.)"""

import json
import threading

import serve_entry


def test_done_is_whole_when_it_appears(tmp_path):
    (tmp_path / "start").write_text("go")
    seen: list = []

    def poll():
        done = tmp_path / "done"
        while not done.exists():
            pass
        seen.append(done.read_text())

    t = threading.Thread(target=poll, daemon=True)
    t.start()
    serve_entry._trace_on_trigger(tmp_path, 0.2)
    t.join(10)
    assert seen and json.loads(seen[0])["ok"] is True, seen
    assert not (tmp_path / "done.part").exists()
    assert list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
