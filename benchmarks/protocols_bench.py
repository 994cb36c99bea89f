"""Paper Table 1 row "Communication Protocols: gRPC vs QUIC" (+ TCP baseline
and the multiplexing knob).

Applies the analytic WAN cost model (core/protocols.py) to the framework's
real sync payloads — uncompressed and compressed deltas of the full-size
stablelm-1.6b parameter set — across link profiles (clean LAN-like,
continental WAN, lossy intercontinental)."""
from __future__ import annotations

import jax

from benchmarks.common import emit, save_results
from repro.configs import get_config
from repro.core.compression import Compressor
from repro.core.protocols import GRPC, QUIC, TCP, Link, sync_wall_time
from repro.models import build_model

LINKS = {
    "clean_10g": Link(latency_s=0.005, bandwidth=1.25e9, loss_rate=1e-6),
    "wan_cross_region": Link(latency_s=0.03, bandwidth=1.25e9, loss_rate=1e-4),
    "lossy_intercontinental": Link(latency_s=0.08, bandwidth=6.25e8, loss_rate=1e-3),
}


def run() -> dict:
    cfg = get_config("stablelm-1.6b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    payloads = {
        "raw": Compressor("none").bytes_per_sync(params),
        "topk1%": Compressor("topk", topk_ratio=0.01).bytes_per_sync(params),
        "int8": Compressor("int8").bytes_per_sync(params),
    }
    rows = {}
    for link_name, link in LINKS.items():
        for pay_name, nbytes in payloads.items():
            for proto in (TCP, GRPC, QUIC):
                t = sync_wall_time(nbytes, 3, proto, link)
                key = f"{link_name}/{pay_name}/{proto.name}"
                rows[key] = {"bytes": nbytes, "seconds": t}
                emit(f"protocols/{key}", t * 1e6, f"sync_s={t:.3f}")
    # multiplexing sweep on the paper's headline case
    link = LINKS["lossy_intercontinental"]
    for n in (1, 2, 4, 8, 16):
        t_grpc = GRPC.transfer_time(payloads["raw"], link, n_streams=n)
        t_quic = QUIC.transfer_time(payloads["raw"], link, n_streams=n)
        rows[f"multiplex/{n}"] = {"grpc": t_grpc, "quic": t_quic}
        emit(f"protocols/multiplex_{n}", t_quic * 1e6,
             f"grpc={t_grpc:.2f}s;quic={t_quic:.2f}s")
    save_results("protocols", rows)
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
