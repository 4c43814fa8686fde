"""Serving security: TLS, form login, pluggable authenticator
(VERDICT r2 item 8; reference: ``water/H2O.java:242-266``, ``h2o-security``)."""

import ssl
import subprocess
import urllib.error
import urllib.request

import pytest

from h2o3_tpu.api import H2OServer


@pytest.fixture(scope="module")
def cert(tmp_path_factory):
    d = tmp_path_factory.mktemp("tls")
    crt, key = d / "srv.crt", d / "srv.key"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(crt), "-days", "1",
         "-subj", "/CN=127.0.0.1"],
        check=True, capture_output=True)
    return str(crt), str(key)


def test_https_serving(cert):
    crt, key = cert
    s = H2OServer(port=0, ssl_certfile=crt, ssl_keyfile=key).start()
    try:
        assert s.url.startswith("https://")
        ctx = ssl.create_default_context()
        ctx.check_hostname = False
        ctx.verify_mode = ssl.CERT_NONE
        with urllib.request.urlopen(f"{s.url}/3/Cloud", context=ctx) as r:
            assert r.status == 200
        # plain http against the TLS port must fail
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://{s.host}:{s.port}/3/Cloud", timeout=3)
    finally:
        s.stop()


def test_h2o_py_connects_over_https(cert, tmp_path):
    """The REAL h2o-py client over https with a self-signed cert."""
    import os
    import sys
    if not os.path.isdir("/root/reference/h2o-py"):
        pytest.importorskip("h2o")     # the child below has to import it
    crt, key = cert
    script = tmp_path / "flow.py"
    script.write_text(f"""
import sys, warnings
warnings.filterwarnings("ignore")
sys.path.insert(0, "/root/reference/h2o-py")
import os
if os.environ.get("JAX_PLATFORMS") == "cpu":
    import jax; jax.config.update("jax_platforms", "cpu")
from h2o3_tpu.api import H2OServer
s = H2OServer(port=0, ssl_certfile={crt!r}, ssl_keyfile={key!r}).start()
import h2o
h2o.connect(url=s.url, verify_ssl_certificates=False,
            strict_version_check=False)
assert h2o.cluster().cloud_healthy
print("HTTPS_OK")
os._exit(0)
""")
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "HTTPS_OK" in proc.stdout


def test_form_login_session_cookie():
    s = H2OServer(port=0, username="u", password="p").start()
    try:
        # no credentials → 401
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{s.url}/3/Cloud")
        assert ei.value.code == 401
        # the login page itself is reachable
        with urllib.request.urlopen(f"{s.url}/login") as r:
            assert b"form" in r.read()
        # bad form login → 401
        bad = urllib.parse.urlencode({"username": "u",
                                      "password": "wrong"}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(f"{s.url}/login", data=bad))
        assert ei.value.code == 401
        # good form login → cookie grants access
        good = urllib.parse.urlencode({"username": "u",
                                       "password": "p"}).encode()
        with urllib.request.urlopen(
                urllib.request.Request(f"{s.url}/login", data=good)) as r:
            cookie = r.headers["Set-Cookie"].split(";")[0]
        req = urllib.request.Request(f"{s.url}/3/Cloud",
                                     headers={"Cookie": cookie})
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
        # logout invalidates the session
        urllib.request.urlopen(urllib.request.Request(
            f"{s.url}/logout", data=b"", headers={"Cookie": cookie}))
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 401
    finally:
        s.stop()


def test_pluggable_authenticator():
    """The LDAP-shaped hook: any (user, password) -> bool callable."""
    import base64
    calls = []

    def ldap_like(user, password):
        calls.append(user)
        return user == "dn=alice" and password == "s3cret"

    s = H2OServer(port=0, authenticator=ldap_like).start()
    try:
        tok = base64.b64encode(b"dn=alice:s3cret").decode()
        req = urllib.request.Request(
            f"{s.url}/3/Cloud", headers={"Authorization": f"Basic {tok}"})
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
        bad = base64.b64encode(b"dn=bob:nope").decode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"{s.url}/3/Cloud", headers={"Authorization": f"Basic {bad}"}))
        assert ei.value.code == 401
        assert "dn=alice" in calls and "dn=bob" in calls
    finally:
        s.stop()
