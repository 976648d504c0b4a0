"""The ``store_sales_item_uniform`` query: the ``store_sales_item`` query
itself, loaded from its file; only the configuration differs, with
``ss_item_sk`` uniform over the items."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_query_store_sales_item_shared",
    Path(__file__).with_name("store_sales_item.py"))
_item = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_item)

build = _item.build
