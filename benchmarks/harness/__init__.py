"""The benchmark's yardstick: nothing here imports ``keystone_tpu``
except ``device.bring_up``, which applies the program's platform rule."""
