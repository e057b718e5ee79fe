"""The chain server as a child of the benchmark: what
`python -m generativeaiexamples_tpu.api.server` does, except that uploads
go under this run's TMPDIR (the program's `main()` fixes them at
/tmp/gaie_tpu/uploaded_files, a path two checkouts would share). Runs
with JAX_PLATFORMS=cpu and remote connectors: it never touches the chip.
"""

from __future__ import annotations

import argparse
import logging
import os
import tempfile


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    from aiohttp import web

    from generativeaiexamples_tpu.api.server import ChainServer
    from generativeaiexamples_tpu.config.wizard import load_config
    from generativeaiexamples_tpu.connectors.factory import uses_local_device

    config = load_config(None)
    if uses_local_device(config):
        raise SystemExit("the benchmark's chain server must be remote-only: "
                         "one process holds the chip")
    uploads = os.path.join(tempfile.gettempdir(), "gaie_bench_uploads")
    server = ChainServer(config, upload_dir=uploads)
    web.run_app(server.app, host=args.host, port=args.port, print=None)


if __name__ == "__main__":
    main()
