"""Launch-failure paths of the fleet launcher surface their cause.

Regression pins for the xmrlint XMR004 fixes: a failed ``launch_workers``
must (a) raise the *original* :class:`WorkerUnavailable` — never a cleanup
error masking it — and (b) log, not swallow, any failure while reaping the
partially-launched fleet. Uses fake worker processes (no subprocess spawn,
no JAX import in children) so the whole module runs in milliseconds.
"""

import json
import logging
import socket
import threading

import pytest

from repro.serving.admission import WorkerUnavailable
from repro.serving.fleet import launcher as launcher_mod
from repro.serving.fleet.launcher import launch_workers


class _FakeStdout:
    def __init__(self, line: str) -> None:
        self._line = line

    def readline(self) -> str:
        line, self._line = self._line, ""
        return line


class _FakeProc:
    """Just enough of subprocess.Popen for the launcher's failure path."""

    def __init__(self, announce_line: str, exit_code=None, kill_raises=False):
        self.stdout = _FakeStdout(announce_line)
        self.pid = 4242
        self._exit_code = exit_code
        self._kill_raises = kill_raises

    def poll(self):
        return self._exit_code

    def terminate(self):
        if self._kill_raises:
            raise RuntimeError("terminate refused (fake)")
        if self._exit_code is None:  # real Popen: no-op once exited
            self._exit_code = -15

    def kill(self):
        if self._kill_raises:
            raise RuntimeError("kill refused (fake)")
        if self._exit_code is None:
            self._exit_code = -9

    def wait(self, timeout=None):
        return self._exit_code


@pytest.fixture
def accept_socket():
    """A listening socket the 'announced' worker port points at, so the
    launcher's WorkerConnection can actually connect."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    accepted = []

    def _accept():
        try:
            while True:
                conn, _ = srv.accept()
                accepted.append(conn)
        except OSError:
            pass  # closed by teardown

    t = threading.Thread(target=_accept, daemon=True)
    t.start()
    yield srv.getsockname()[1]
    srv.close()
    for conn in accepted:
        conn.close()


def _fake_popen_factory(procs):
    it = iter(procs)

    def _factory(*args, **kwargs):
        return next(it)

    return _factory


def test_launch_failure_surfaces_cause(monkeypatch, accept_socket):
    """Worker 1 dying pre-announce raises WorkerUnavailable naming the exit
    code — the diagnosis the old silent cleanup used to bury."""
    announce = json.dumps({"port": accept_socket, "pid": 4242}) + "\n"
    procs = [
        _FakeProc(announce),
        _FakeProc("", exit_code=1),  # died before announcing
    ]
    monkeypatch.setattr(launcher_mod.subprocess, "Popen",
                        _fake_popen_factory(procs))
    with pytest.raises(WorkerUnavailable) as err:
        launch_workers(2, startup_timeout_s=5.0, rpc_timeout_s=5.0)
    msg = str(err.value)
    assert "no announcement" in msg
    assert "exit code 1" in msg


def test_launch_cleanup_failure_is_logged_not_masking(
    monkeypatch, accept_socket, caplog
):
    """A cleanup kill() blowing up during the reap must not replace the
    original launch error; it is logged as a warning instead."""
    announce = json.dumps({"port": accept_socket, "pid": 4242}) + "\n"
    procs = [
        _FakeProc(announce, kill_raises=True),  # reap of this one fails
        _FakeProc("", exit_code=1),
    ]
    monkeypatch.setattr(launcher_mod.subprocess, "Popen",
                        _fake_popen_factory(procs))
    with caplog.at_level(logging.WARNING, logger=launcher_mod.log.name):
        with pytest.raises(WorkerUnavailable):  # the original cause, not RuntimeError
            launch_workers(2, startup_timeout_s=5.0, rpc_timeout_s=5.0)
    assert any(
        "launch cleanup" in rec.message and "kill" in rec.message
        for rec in caplog.records
    )


def test_launch_under_tpu_parent_raises_at_once(monkeypatch):
    """A parent that holds a TPU cannot hand the chip to workers: launch
    refuses before spawning anything, instead of waiting out the
    announcement timeout."""
    import jax

    from repro.serving.fleet import ChipHeldError

    def _no_spawn(*args, **kwargs):
        raise AssertionError("launch_workers spawned a worker")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(launcher_mod.subprocess, "Popen", _no_spawn)
    with pytest.raises(ChipHeldError, match="one process"):
        launch_workers(2, startup_timeout_s=120.0)
