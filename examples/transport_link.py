"""Minimal real sender->receiver link over a loopback socket.

The paper's deployment shape end to end: a ``TransportServer`` (edge node)
in a background thread, and two ``SenderClient``s (IoT nodes) on the same
process -- one shipping raw windows, one running the SymED compressor
locally and shipping only finished piece tuples.  Both receive the edge's
symbol-delta frames back over the socket; the pieces sender demonstrates
the paper's headline wire saving.

    PYTHONPATH=src python examples/transport_link.py
"""
import numpy as np

from repro.core.symed import SymEDConfig
from repro.data.synthetic import make_fleet
from repro.launch.stream import StreamServer
from repro.launch.transport import (
    SenderClient, ServeThread, TransportServer, session_seed)

N_STREAMS, LENGTH, WINDOW = 3, 256, 32


def run_sender(port: int, cfg: SymEDConfig, mode: str, data: np.ndarray):
    client = SenderClient("127.0.0.1", port, cfg, mode=mode)
    sids = [f"{mode}-{i}" for i in range(len(data))]
    for sid in sids:
        client.open(sid, session_seed(sid, 0))
    for c in range(0, LENGTH, WINDOW):          # interleave the sessions
        for i, sid in enumerate(sids):
            client.send(sid, data[i, c: c + WINDOW])
    results = {sid: client.close(sid) for sid in sids}
    symbols = sum(r["n_pieces"] for r in results.values())
    points = sum(r["t_seen"] for r in results.values())
    print(f"  {mode:>6} sender: {len(sids)} sessions, {points} points -> "
          f"{symbols} symbols, {int(client.payload_bytes)} payload B "
          f"({client.payload_bytes / (4 * points):.3f} of raw)")
    client.shutdown()


def main():
    cfg = SymEDConfig(tol=0.5, alpha=0.01, n_max=256, k_max=32, len_max=256)
    server = StreamServer(cfg, max_sessions=8, window_cap=WINDOW,
                          digitize_every_k=1, autoscale=True, min_slots=1)
    transport = TransportServer(server, port=0)
    serving = ServeThread(transport, expect_sessions=2 * N_STREAMS)
    print(f"edge receiver listening on 127.0.0.1:{transport.port}")

    data = np.asarray(make_fleet(N_STREAMS, LENGTH, seed=4))
    with serving.root_cause():
        for mode in ("pieces", "raw"):
            run_sender(transport.port, cfg, mode, data)
    serving.join(timeout=60)

    rep = server.report(1.0)
    print(f"edge totals: {int(rep['points_in'])} points in, "
          f"{int(rep['wire_in_bytes'])} wire-in B "
          f"(ratio {rep['wire_in_ratio']:.3f}), "
          f"{int(rep['bytes_out'])} wire-out B in "
          f"{int(rep['frames_out'])} delta frames; "
          f"table grew {int(rep['grows'])}x, shrank {int(rep['shrinks'])}x")


if __name__ == "__main__":
    main()
