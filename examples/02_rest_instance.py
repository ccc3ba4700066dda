"""A full single-process instance with the REST gateway.

Run: python examples/02_rest_instance.py
Then explore (default credentials admin/password):

    TOKEN=$(curl -s -u admin:password -X POST \
        http://127.0.0.1:8080/authapi/jwt | python -c \
        'import json,sys; print(json.load(sys.stdin)["token"])')
    curl -s -H "Authorization: Bearer $TOKEN" \
        http://127.0.0.1:8080/api/system/version
    curl -s http://127.0.0.1:8080/api/openapi.json | head

Ctrl-C stops it.
"""

# Demos run on the CPU whatever JAX_PLATFORMS says, so they behave the same
# on any host. To run one on a TPU, delete these two lines.
import jax

jax.config.update("jax_platforms", "cpu")


import time

from sitewhere_tpu.instance import SiteWhereInstance
from sitewhere_tpu.web.server import RestServer


def main():
    instance = SiteWhereInstance(instance_id="example",
                                 data_dir="/tmp/swtpu-example")
    instance.start()
    rest = RestServer(instance, port=8080)
    rest.start()
    print(f"REST gateway: {rest.base_url}")
    print("OpenAPI doc:", rest.base_url + "/api/openapi.json")
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        pass
    finally:
        rest.stop()
        instance.stop()


if __name__ == "__main__":
    main()
