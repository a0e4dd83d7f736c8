"""Test environment: force an 8-device virtual CPU mesh so every sharding /
collective path is exercised without TPU hardware.  What only a chip can
show — Mosaic compiling the kernels, what a DMA leaves in HBM, collectives
over ICI — is chip_smoke.py's job, run on the machine that has one.

The platform and device count must be in place before the first backend
initializes; pytest imports conftest before the test modules, so this runs
ahead of every `import dmlc_core_tpu` / `import jax` in the tests.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


# ---- shared TLS test plumbing (used by test_tls.py and test_tls_s3.py) ------

def make_tls_server(tmpdir, handler_factory):
    """Self-signed cert (SAN: 127.0.0.1/localhost) + a TLS-wrapped HTTPServer
    serving on a daemon thread.  Returns {"httpd", "port", "cert"}; caller
    shuts down via httpd.shutdown()."""
    import ssl
    import subprocess
    import threading
    from http.server import HTTPServer
    from pathlib import Path

    tmpdir = Path(tmpdir)
    cert, key = tmpdir / "cert.pem", tmpdir / "key.pem"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "2",
         "-subj", "/CN=localhost",
         "-addext", "subjectAltName=IP:127.0.0.1,DNS:localhost"],
        check=True, capture_output=True)
    httpd = HTTPServer(("127.0.0.1", 0), handler_factory)
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(str(cert), str(key))
    httpd.socket = ctx.wrap_socket(httpd.socket, server_side=True)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return {"httpd": httpd, "port": httpd.server_address[1],
            "cert": str(cert)}
