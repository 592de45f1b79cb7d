"""Fleet partition worker: one process, one label partition.

Run as ``python -m repro.serving.fleet.worker --host 127.0.0.1 --port 0``.
The worker binds (port 0 = ephemeral), prints one JSON line with the bound
port + pid on stdout, then serves length-prefixed RPC frames
(:mod:`repro.serving.fleet.rpc`) until a ``shutdown`` op or EOF.

Ops:

``ping``
    liveness probe — replies immediately.
``load``
    receive one partition's sliced layer tensors + the global tree metadata
    and build the local :class:`~repro.core.tree.XMRTree`.
``begin`` / ``step``
    the partition half of the pipelined exchange protocol (see
    :class:`~repro.index.planner.BeamTransport`), executed by
    :class:`PartitionRunner` through the *same jitted programs* the
    in-process planner uses (``_owned_level_scores`` / ``_spec_select`` /
    ``_reconcile_select``) — which is what keeps fleet-served results
    bitwise-identical to in-process serving.
``shutdown``
    reply, then exit cleanly.

Scheduling inside ``begin``/``step`` mirrors the in-process pipelined
planner: the cheap local select is dispatched first, its tiny beam is
materialized and sent back, and the *speculative* next-level MSCM is
dispatched before the reply is written — JAX async dispatch keeps the heavy
matmul running on this worker's device while the coordinator merges beams.
"""

from __future__ import annotations

# xmrlint: single-threaded — one accept loop, one connection, no concurrent
# frame writers on this socket; the coordinator side carries the lock.
import argparse
import json
import os
import signal
import socket
import struct
import sys
import traceback
from typing import List, Optional, Tuple

import numpy as np

from repro.serving.fleet.rpc import recv_frame, send_frame

_NEEDS_DENSE = ("mscm_dense", "mscm_pallas", "mscm_pallas_pregather")


class PartitionRunner:
    """One partition's half of the pipelined beam-exchange protocol."""

    def __init__(
        self,
        header: dict,
        arrays: List[np.ndarray],
    ) -> None:
        import jax.numpy as jnp

        from repro.core.tree import TreeLayerArrays, XMRTree

        self.pid = int(header["pid"])
        self.level = int(header["level"])          # split level li0
        self.n_cols = tuple(header["n_cols"])      # GLOBAL per-level counts
        self.branching = tuple(header["branching"])
        self.chunk_start = int(header["chunk_start"])
        self.beam = int(header["beam"])
        self.topk = int(header["topk"])
        self.method = str(header["method"])
        self.score_mode = str(header["score_mode"])
        self.qt = int(header["qt"])
        self.tier = str(header.get("tier", "exact"))
        d = int(header["d"])
        if self.tier != "exact":
            # Quantized payload: three tensors per layer (exact mask, int8
            # weights, f32 scale rows) — see ``partition_payload``. The
            # local sub-tree is a QuantizedTree; the shared jitted programs
            # dispatch on the quantized method string.
            from repro.quant import QuantLayerArrays, QuantizedTree

            n_layers = len(arrays) // 3
            qlayers = [
                QuantLayerArrays(
                    chunk_rows=jnp.asarray(arrays[3 * i]),
                    chunk_vals=jnp.asarray(arrays[3 * i + 1]),
                    chunk_scales=jnp.asarray(arrays[3 * i + 2]),
                )
                for i in range(n_layers)
            ]
            self.part = QuantizedTree(
                layers=qlayers,
                n_cols=tuple(header["part_n_cols"]),
                branching=self.branching[self.level:],
                d=d,
                tier=self.tier,
            )
        else:
            n_layers = len(arrays) // 4
            layers = [
                TreeLayerArrays(
                    chunk_rows=jnp.asarray(arrays[4 * i]),
                    chunk_vals=jnp.asarray(arrays[4 * i + 1]),
                    col_rows=jnp.asarray(arrays[4 * i + 2]),
                    col_vals=jnp.asarray(arrays[4 * i + 3]),
                )
                for i in range(n_layers)
            ]
            self.part = XMRTree(
                layers=layers,
                n_cols=tuple(header["part_n_cols"]),
                branching=self.branching[self.level:],
                d=d,
            )
        # per-batch state (the effective beam/qt default to the loaded
        # full settings; begin() may narrow them for one batch — adaptive
        # beam tiers are coordinator-chosen, the worker just obeys)
        self._beam = self.beam
        self._qt = self.qt
        self._xi = self._xv = self._xd = None
        self._spec_ids = self._spec_comb = None

    @property
    def depth(self) -> int:
        return len(self.n_cols)

    def _span(self, li: int) -> int:
        """Branching product between the split level and ``li``."""
        return int(
            np.prod(self.branching[self.level:li], dtype=np.int64)
        ) if li > self.level else 1

    def _next_b(self, li: int) -> int:
        is_last = li == self.depth - 1
        return min(self.topk if is_last else self._beam, self.n_cols[li])

    def _owned(self, li, parent_ids, parent_scores):
        """One level's owned combined scores through the shared jit."""
        import jax.numpy as jnp

        from repro.index.planner import _owned_level_scores

        lay = self.part.layers[li - self.level]
        c_real = lay.chunk_rows.shape[0] - 1  # minus phantom pad
        return _owned_level_scores(
            lay, self.branching[li], self.part.d,
            self._xi, self._xv, self._xd, parent_ids, parent_scores,
            jnp.int32(self.chunk_start * self._span(li)), jnp.int32(c_real),
            method=self.method, score_mode=self.score_mode, qt=self._qt,
        )

    def _speculate(self, li: int, beam_ids, beam_scores) -> None:
        """Dispatch the level-``li+1`` speculative expansion (async)."""
        if li + 1 < self.depth:
            self._spec_comb, _ = self._owned(li + 1, beam_ids, beam_scores)
            self._spec_ids = beam_ids
        else:
            self._spec_ids = self._spec_comb = None

    def begin(
        self, xi: np.ndarray, xv: np.ndarray,
        parent_ids: np.ndarray, scores: np.ndarray,
        *, beam: Optional[int] = None, qt: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        import jax.numpy as jnp

        from repro.index.planner import _scatter_dense, _spec_select

        # Per-batch tier override: the coordinator's begin header may
        # narrow beam/qt for this batch only; the loaded settings are the
        # default and are restored by the next begin without an override.
        self._beam = self.beam if beam is None else int(beam)
        self._qt = self.qt if qt is None else int(qt)
        li = self.level
        self._xi = jnp.asarray(xi)
        self._xv = jnp.asarray(xv)
        self._xd = (
            _scatter_dense(self._xi, self._xv, self.part.d)
            if self.method in _NEEDS_DENSE else None
        )
        ids = jnp.asarray(parent_ids)
        sc = jnp.asarray(scores)
        comb, own = self._owned(li, ids, sc)
        b_ids, b_sc = _spec_select(
            ids, comb, own,
            n_cols=self.n_cols[li], n_chunks=self.n_cols[li - 1],
            next_b=self._next_b(li),
        )
        self._speculate(li, b_ids, b_sc)
        return np.asarray(b_ids), np.asarray(b_sc)

    def step(
        self, li: int, winner_ids: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        import jax.numpy as jnp

        from repro.index.planner import _reconcile_select

        if self._spec_ids is None:
            raise RuntimeError(f"step(level={li}) before begin/speculation")
        lay = self.part.layers[li - self.level]
        b_ids, b_sc = _reconcile_select(
            jnp.asarray(winner_ids), self._spec_ids, self._spec_comb,
            jnp.int32(self.chunk_start * self._span(li)),
            jnp.int32(lay.chunk_rows.shape[0] - 1),
            n_cols=self.n_cols[li], n_chunks=self.n_cols[li - 1],
            next_b=self._next_b(li),
        )
        self._speculate(li, b_ids, b_sc)
        return np.asarray(b_ids), np.asarray(b_sc)


def _serve_connection(conn: socket.socket, state: dict) -> bool:
    """Serve one client connection. Returns True on a ``shutdown`` op."""
    while True:
        try:
            header, arrays = recv_frame(conn)
        except (EOFError, OSError):
            return False  # client gone; go back to accept()
        except (ValueError, KeyError, TypeError, struct.error):
            # Corrupt frame (oversized length prefix, malformed header):
            # the stream position is unknowable — drop this connection and
            # keep serving. The worker must survive garbage on the wire.
            traceback.print_exc(file=sys.stderr)
            return False
        op = header.get("op", "")
        try:
            if op == "ping":
                send_frame(conn, {"ok": True, "pid": os.getpid(),
                                  "loaded": state.get("runner") is not None})
            elif op == "load":
                state["runner"] = PartitionRunner(header, arrays)
                send_frame(conn, {"ok": True})
            elif op == "begin":
                ids, sc = state["runner"].begin(
                    *arrays,
                    beam=header.get("beam"), qt=header.get("qt"),
                )
                send_frame(conn, {"ok": True}, [ids, sc])
            elif op == "step":
                ids, sc = state["runner"].step(int(header["level"]), arrays[0])
                send_frame(conn, {"ok": True}, [ids, sc])
            elif op == "shutdown":
                send_frame(conn, {"ok": True})
                return True
            else:
                send_frame(conn, {"ok": False, "error": f"unknown op {op!r}"})
        except Exception as exc:  # noqa: BLE001 — report, keep serving
            traceback.print_exc(file=sys.stderr)
            try:
                send_frame(
                    conn,
                    {"ok": False, "error": f"{type(exc).__name__}: {exc}"},
                )
            except OSError:
                return False


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 = ephemeral (bound port printed on stdout)")
    args = ap.parse_args(argv)

    if hasattr(signal, "SIGTERM"):
        # Graceful stop (WorkerHandle.kill's grace window). Flush and exit
        # immediately: raising SystemExit from a handler mid-exchange would
        # unwind through library frames and spew tracebacks at teardown.
        def _on_sigterm(*_):
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(0)

        signal.signal(signal.SIGTERM, _on_sigterm)

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind((args.host, args.port))
    srv.listen(1)
    print(json.dumps({"port": srv.getsockname()[1], "pid": os.getpid()}),
          flush=True)

    state: dict = {"runner": None}
    try:
        while True:
            conn, _ = srv.accept()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            try:
                if _serve_connection(conn, state):
                    return 0
            finally:
                conn.close()
    finally:
        srv.close()


if __name__ == "__main__":
    sys.exit(main())
