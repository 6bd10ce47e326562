select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= 8766 and l_shipdate < 9131
  and l_discount >= 0.05 and l_discount <= 0.07
  and l_quantity < 24
