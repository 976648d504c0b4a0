"""Plain NumPy reference of ``store_sales_item_uniform``: the reference of
``store_sales_item``, loaded from its file; imports nothing of the
program."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_ref_store_sales_item_shared",
    Path(__file__).with_name("store_sales_item_ref.py"))
_item = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_item)

reference = _item.reference
