"""Remote offload demo: client pipeline sends frames to a server pipeline
over TCP (run both ends in one process for the demo; they can be separate
hosts). Both ends use async_depth so remote device round trips overlap
instead of serializing (~30x throughput at round 5, against a server whose
device round trip was ~70 ms; set both to 1 for the reference's strict
synchronous per-buffer semantics).

    python examples/remote_offload.py
"""

import _bootstrap  # noqa: F401  (repo-root import shim for source checkouts)

import time

import numpy as np

from nnstreamer_tpu.core import Caps, TensorsConfig, TensorsInfo
from nnstreamer_tpu.graph import Pipeline


def main() -> None:
    server = Pipeline("server")
    ssrc = server.add_new("tensor_query_serversrc", port=0, id=0,
                          dims="3:64:64:1", types="uint8")
    filt = server.add_new("tensor_filter",
                          model="zoo://mobilenet_v2?width=0.25&size=64"
                                "&num_classes=10&dtype=float32")
    ssink = server.add_new("tensor_query_serversink", id=0, async_depth=16)
    Pipeline.link(ssrc, filt, ssink)
    server.start()
    time.sleep(0.3)
    port = ssrc.bound_port
    print(f"server listening on :{port}")

    client = Pipeline("client")
    rng = np.random.default_rng(0)
    src = client.add_new(
        "appsrc",
        caps=Caps.tensors(TensorsConfig(
            TensorsInfo.from_strings("3:64:64:1", "uint8"), 30)),
        data=[rng.integers(0, 255, (1, 64, 64, 3)).astype(np.uint8)
              for _ in range(10)])
    qc = client.add_new("tensor_query_client", port=port, async_depth=16)
    sink = client.add_new("tensor_sink",
                          new_data=lambda b: print(
                              f"frame {b.offset}: logits "
                              f"{np.asarray(b.memories[0].host())[0, :3]}..."))
    Pipeline.link(src, qc, sink)
    client.run(timeout=300)
    server.stop()


if __name__ == "__main__":
    main()
