"""The control: the plain reference put in the server's place.

A small threaded HTTP server that answers the cell's own URLs from the
seeded array with the numpy reference and PIL's PNG encoder, and can
break one guarantee the configuration states:

  --mode sound      the reference as it is (must come out correct)
  --mode lowered    samples with the low byte dropped (16 -> 8 bits):
                    the nearest precision below the configuration's
  --mode degraded   right pixels, but marked X-OMPB-Degraded
  --mode one_pixel  right but for one sample of every 7th answer,
                    altered where the answer is produced
  --mode host       right pixels, but no answer counted as encoded on
                    the device (`tile_device_lanes_total` stays 0)

It never runs in a benchmark run: `benchmarks/tests/` and
`benchmarks/tests/control_run.py` start it through
`run_cell(..., server_command=...)`.

    python control_server.py --mode M --reference tile --seed N
        --image-json '{...}' -- --dev --registry R --config C --port P
"""

import argparse
import io
import json
import os
import sys
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
from PIL import Image

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness.cell import load_plugin  # noqa: E402
from benchmarks.harness.fixture import seeded_planes  # noqa: E402


def parse_request(path: str):
    """The generator's request dict back from a /tile URL."""
    url = urllib.parse.urlsplit(path)
    parts = url.path.strip("/").split("/")
    query = dict(urllib.parse.parse_qsl(url.query))
    return {
        "z": int(parts[2]), "c": int(parts[3]),
        "x": int(query["x"]), "y": int(query["y"]),
        "w": int(query["w"]), "h": int(query["h"]),
    }


def main() -> None:
    split = sys.argv.index("--")
    parser = argparse.ArgumentParser()
    parser.add_argument("--mode", required=True)
    parser.add_argument("--reference", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--image-json", required=True)
    args = parser.parse_args(sys.argv[1:split])
    served = argparse.ArgumentParser()
    served.add_argument("--port", type=int, required=True)
    port = served.parse_known_args(sys.argv[split + 1:])[0].port
    image = json.loads(args.image_json)
    reference = load_plugin("reference", args.reference)
    data = seeded_planes(args.seed, image["size_x"], image["size_y"],
                         image["size_z"], image["size_c"])
    render = reference.lowered if args.mode == "lowered" else (
        reference.expected)
    answered, lock = [0], threading.Lock()

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _send(self, status, body, ctype, extra=()):
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for key, value in extra:
                self.send_header(key, value)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path.startswith("/healthz"):
                body = json.dumps({
                    "tile_device_lanes_total": (
                        0 if args.mode == "host" else answered[0]),
                    "engine": "device", "engine_reason": "control",
                    "device": {"platform": "control",
                               "kind": f"reference:{args.mode}", "count": 1},
                    "cache": {"device_planes": {"planes": 1 << 20}},
                }).encode()
                return self._send(200, body, "application/json")
            if self.path.startswith("/metrics"):
                return self._send(200, b"", "text/plain")
            pixels = np.array(render(data, parse_request(self.path)))
            with lock:
                answered[0] += 1
                n = answered[0]
            if args.mode == "one_pixel" and n % 7 == 0:
                pixels.flat[pixels.size // 3] ^= 1
            out = io.BytesIO()
            Image.fromarray(pixels).save(out, "PNG", compress_level=1)
            extra = ((("X-OMPB-Degraded", "control"),)
                     if args.mode == "degraded" else ())
            self._send(200, out.getvalue(), "image/png", extra)

    ThreadingHTTPServer.daemon_threads = True
    ThreadingHTTPServer(("127.0.0.1", port), Handler).serve_forever()


if __name__ == "__main__":
    main()
