"""End-to-end trace demo workload (reference analog:
scripts/pytorch/linear_model_example.py, upgraded to the flagship
transformer).

Run next to a daemon, then trigger a trace:

    build/src/dynologd --enable_ipc_monitor &
    python examples/train_demo.py --job-id 42 &
    build/src/dyno gputrace --job_id 42 --duration_ms 500 --log_file /tmp/t.json
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--job-id", type=int, default=0)
    parser.add_argument("--steps", type=int, default=0, help="0 = run forever")
    parser.add_argument("--endpoint", default="dynolog")
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=256)
    args = parser.parse_args()

    from dynolog_tpu._jaxinit import enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax

    from dynolog_tpu.client import TraceClient
    from dynolog_tpu.models.train import (
        make_batch, make_train_state, make_train_step)
    from dynolog_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig()
    params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg)
    step = make_train_step(cfg)
    batch = make_batch(
        jax.random.PRNGKey(1), cfg, args.batch_size, args.seq_len)

    client = TraceClient(job_id=args.job_id, endpoint=args.endpoint)
    registered = client.start()
    print(f"devices={jax.devices()} daemon_registered={registered} "
          f"compile_cache={cache_dir}")

    i = 0
    try:
        while args.steps == 0 or i < args.steps:
            params, opt_state, loss = step(params, opt_state, batch)
            client.step()
            i += 1
            if i % 50 == 0:
                print(f"step {i} loss {float(loss):.4f}", flush=True)
    except KeyboardInterrupt:
        pass
    finally:
        client.stop()
    print(f"done after {i} steps; traces captured: {client.traces_completed}")


if __name__ == "__main__":
    main()
