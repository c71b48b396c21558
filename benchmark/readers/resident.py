"""MB the connector's ``SplitStore`` holds in the device's memory at the
window's end (``scan_store.device_bytes``). A connector without a store,
or a store without a device tier (a program from before it), gives
nothing to read; a tier that holds nothing reads 0.0."""


def read(ctx: dict, selector: dict):
    store = getattr(ctx["conn"], "scan_store", None)
    held = getattr(store, "device_bytes", None)
    return None if held is None else held / 1e6
