"""Drive a running server over REST with the stdlib client — the same
endpoints h2o-py uses.

    python -m h2o3_tpu.launch --serve &      # on the server host
    JAX_PLATFORMS=cpu python examples/rest_client_flow.py http://host:54321
"""
import os
import sys

import numpy as np

from h2o3_tpu.api import H2OClient, H2OServer


def main(url: str | None):
    server = None
    if url is None:                 # self-contained demo: embed a server
        server = H2OServer(port=0).start()
        url = server.url
    c = H2OClient(url)
    print("cloud:", c.cloud_status()["cloud_name"])

    import os
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".csv", delete=False) as f:
        rng = np.random.default_rng(2)
        f.write("a,b,y\n")
        for i in range(500):
            a, b = rng.normal(), rng.normal()
            f.write(f"{a},{b},{'t' if a + b > 0 else 'f'}\n")
        path = f.name
    try:
        # upload_file ships the CLIENT-LOCAL csv through POST /3/PostFile,
        # so this works against a remote server too (import_file would
        # resolve the path on the SERVER's filesystem)
        key = c.upload_file(path)
        model = c.train("gbm", key, y="y", ntrees=10, max_depth=3)
        mm = model["output"]["training_metrics"]
        print("trained", model["model_id"]["name"], "auc:",
              round(mm["auc"], 4))
        pred_key = c.predict(model["model_id"]["name"], key)
        print("prediction frame:", pred_key)
    finally:
        os.unlink(path)
        if server is not None:
            server.stop()


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
