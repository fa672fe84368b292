"""One routable serving-engine replica process for the router drill
(tools/router_drill.py) and the multi-process router tests.

Extends the fleet_replica_worker skeleton with the request path:
an EngineGateway drives the engine's step loop on its own thread and
mounts ``POST /v1/generate`` next to the GET debug surface, so the
parent routes real traffic over the wire — then SIGKILLs this process
mid-request to prove failover.

Every worker builds the SAME seeded tiny GPT (paddle.seed(7)), so
greedy streams are bit-exact across replicas — the property the
router's journal replay relies on and the drill asserts.

Prints ONE JSON ready-line ``{"port": ..., "replica_id": ...}`` after
warmup, then sleeps until killed.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.serving.router import EngineGateway  # noqa: E402
from paddle_tpu.text.models import (  # noqa: E402
    GPTForCausalLM, TransformerLMConfig,
)


def main():
    paddle.seed(7)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    # ROUTER_ROLE stamps this replica into a disaggregated tier
    # (prefill|decode|monolithic)
    role = os.environ.get("ROUTER_ROLE", "monolithic")
    eng = ServingEngine(
        m, num_slots=2, bucket_min=8, role=role,
        replica_id=os.environ.get("ROUTER_REPLICA_ID"),
        slo_ttft_ms=60000.0)
    gateway = EngineGateway(eng)
    # warm the compile inventory BEFORE declaring ready — the
    # drill's prefill bucket plus decode, so its steady-state
    # compile audit sees zero compiles under traffic
    rs = np.random.RandomState(0)
    solo = gateway.submit(rs.randint(0, 97, (5,)).astype(np.int64),
                          max_new_tokens=4)
    gateway.wait(solo, timeout=120.0)
    if role != "monolithic":
        # warm the KV export/import programs too: the disagg drill's
        # steady-state compile audit covers handoff traffic
        with gateway._lock:
            eng.warmup_kv_handoff()
    eng.declare_warmup()
    handle = gateway.serve(port=int(os.environ.get("ROUTER_PORT",
                                                   "0")))
    print(json.dumps({"port": handle.port,
                      "replica_id": eng.replica_id}), flush=True)
    while True:
        time.sleep(0.1)


if __name__ == "__main__":
    main()
